"""8-device checks: radix-4 tree psum == flat psum; RS+AG tree; compressed
int8 reduction exactness + error feedback."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.collectives import (factor_radix4, make_tree_mesh,
                                    tree_psum, tree_reduce_scatter_gather)
from repro.dist.plan import make_reduction_plan
from repro.optim.compression import compressed_psum_mean

assert len(jax.devices()) == 8

# ---- factorization
assert factor_radix4(16) == (4, 4)
assert factor_radix4(32) == (4, 4, 2)
assert factor_radix4(8) == (4, 2)
assert factor_radix4(6) == (3, 2)

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
tmesh, sub = make_tree_mesh(mesh, "data")
assert sub == ("data_t0", "data_t1") and tmesh.shape["data_t0"] == 4

x = jnp.arange(8 * 5, dtype=jnp.float32).reshape(8, 5)

def tree_fn(xl):
    return tree_psum(xl, sub)

def flat_fn(xl):
    return jax.lax.psum(xl, sub)  # same axes, single fused reduction

got = jax.jit(jax.shard_map(tree_fn, mesh=tmesh, in_specs=P(sub),
                            out_specs=P(sub)))(x)
want = jnp.broadcast_to(x.sum(axis=0, keepdims=True), x.shape)
np.testing.assert_allclose(np.asarray(got), np.asarray(want))

# ---- RS+AG tree path
v = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)

def rs_fn(xl):
    return tree_reduce_scatter_gather(xl[0], sub)[None]

got2 = jax.jit(jax.shard_map(rs_fn, mesh=tmesh, in_specs=P(sub),
                             out_specs=P(sub)))(v)
np.testing.assert_allclose(np.asarray(got2),
                           np.broadcast_to(v.sum(0), (8, 16)))

# ---- RS+AG rejects unscatterable payloads AT TRACE TIME (13 not divisible
# by the 8-device tree), pointing the caller at tree_psum instead
bad = jnp.ones((8, 13), jnp.float32)
try:
    jax.jit(jax.shard_map(rs_fn, mesh=tmesh, in_specs=P(sub),
                          out_specs=P(sub)))(bad)
except ValueError as e:
    assert "use tree_psum for unscatterable payloads" in str(e), e
else:
    raise AssertionError("unscatterable payload did not raise")

# ---- int32 payloads under the carry-width audit: the staged tree psum is
# BIT-exact against the flat fused psum (integer adds commute exactly; the
# audit proves 8 x int8-grid operands cannot overflow the int32 carrier)
plan8 = make_reduction_plan(8, payload_bits=8, acc_bits=32)
assert plan8.accum is not None and plan8.accum.spill_bits <= 32
xi = jnp.asarray(np.random.default_rng(1).integers(-128, 128, (8, 7)),
                 jnp.int32)

def tree_int_fn(xl):
    return tree_psum(xl, sub, plan=plan8)

got_i = jax.jit(jax.shard_map(tree_int_fn, mesh=tmesh, in_specs=P(sub),
                              out_specs=P(sub)))(xi)
want_i = jax.jit(jax.shard_map(flat_fn, mesh=tmesh, in_specs=P(sub),
                               out_specs=P(sub)))(xi)
assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
assert np.array_equal(np.asarray(got_i),
                      np.broadcast_to(np.asarray(xi).sum(0), (8, 7)))
assert np.asarray(got_i).dtype == np.int32

# ---- compressed reduction: exact for int payloads scaled into int8 range
g_int = jnp.asarray(
    np.random.default_rng(0).integers(-60, 60, (8, 33)), jnp.float32)
err0 = jnp.zeros((8, 33), jnp.float32)

def comp_fn(g, e):
    grads = {"w": g[0]}
    errs = {"w": e[0]}
    mean, new_err = compressed_psum_mean(grads, errs, sub, 8)
    return mean["w"][None], new_err["w"][None]

mean, new_err = jax.jit(jax.shard_map(
    comp_fn, mesh=tmesh, in_specs=(P(sub), P(sub)),
    out_specs=(P(sub), P(sub))))(g_int, err0)
# integer grid payloads with shared scale: mean can carry tiny fp error only
np.testing.assert_allclose(np.asarray(mean)[0], np.asarray(g_int).mean(0),
                           atol=0.5)
# error feedback: residual + dequantized == original gradient (exactly)
# reconstruct: q*scale = g - err  ->  (g - err) summed/8 == mean
recon = (np.asarray(g_int) - np.asarray(new_err)).mean(0)
np.testing.assert_allclose(recon, np.asarray(mean)[0], rtol=1e-6, atol=1e-6)

print("OK collectives")
