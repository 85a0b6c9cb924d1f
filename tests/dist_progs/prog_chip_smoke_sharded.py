"""``chip_smoke.py``'s four-chip body on 4 virtual CPU devices at a
reduced config: a mesh_shards=4 engine against a single-device engine on
the same seeded requests, which must agree bit for bit."""
import importlib.util
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config

assert len(jax.devices()) == 4
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    os.pardir, os.pardir)
spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(chip_smoke)

cfg = get_config("llama3.2-3b").reduced(dtype=jnp.float32)
res = chip_smoke.smoke_sharded(cfg, shards=4, slots=8, max_seq=64,
                               prefill_chunk=16, n_requests=8,
                               prompt_lens=(16, 48), gen=8)
assert all(res["checks"].values()), res
figs = res["figures"]
assert len(set(figs["mesh_devices"])) == 4
assert figs["logit_rows_compared"] > 0
print("OK chip smoke sharded")
