"""Property tests for the autotuner's Pareto-dominance utilities
(:mod:`repro.tune.pareto`) — pure host logic, no jax."""
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.tune.pareto import argbest, dominates, pareto_front

# three mixed-direction objectives over small integer metrics: small value
# ranges force ties, duplicates and dense dominance chains
OBJS = (("x", "max"), ("y", "min"), ("z", "max"))


def _points(data, max_points=12):
    n = data.draw(st.integers(min_value=1, max_value=max_points))
    return [{"x": data.draw(st.integers(min_value=0, max_value=4)),
             "y": data.draw(st.integers(min_value=0, max_value=4)),
             "z": data.draw(st.integers(min_value=0, max_value=4))}
            for _ in range(n)]


# ------------------------------------------------------------ unit checks

def test_dominates_basic():
    a = {"x": 2, "y": 1, "z": 3}
    b = {"x": 1, "y": 2, "z": 3}
    assert dominates(a, b, OBJS)          # better x, better (smaller) y
    assert not dominates(b, a, OBJS)
    assert not dominates(a, a, OBJS)      # irreflexive: no strict edge
    # mixed: each better somewhere -> incomparable
    c = {"x": 3, "y": 2, "z": 3}
    assert not dominates(a, c, OBJS) and not dominates(c, a, OBJS)


def test_direction_validated():
    with pytest.raises(ValueError, match="max.*min|min.*max"):
        dominates({"x": 1}, {"x": 2}, (("x", "up"),))


def test_duplicates_all_kept_on_front():
    pts = [{"x": 1, "y": 1, "z": 1}, {"x": 1, "y": 1, "z": 1},
           {"x": 0, "y": 2, "z": 0}]
    assert pareto_front(pts, OBJS) == [0, 1]


def test_argbest_directions_and_ties():
    pts = [{"x": 1}, {"x": 3}, {"x": 3}, {"x": 0}]
    assert argbest(pts, "x", "max") == 1   # first index wins the tie
    assert argbest(pts, "x", "min") == 3
    with pytest.raises(ValueError, match="empty"):
        argbest([], "x")


# ------------------------------------------------------- property checks

@settings(max_examples=30, deadline=None)
@given(st.data())
def test_front_mutually_non_dominated(data):
    """No member of the front dominates another member."""
    pts = _points(data)
    front = pareto_front(pts, OBJS)
    assert front, "a non-empty finite set always has a maximal element"
    for i in front:
        for j in front:
            assert not dominates(pts[i], pts[j], OBJS), (pts[i], pts[j])


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_dropped_points_dominated_by_a_front_member(data):
    """Every point NOT on the front is dominated by some front member —
    the front loses no undominated trade-off."""
    pts = _points(data)
    front = set(pareto_front(pts, OBJS))
    for i, p in enumerate(pts):
        if i in front:
            continue
        assert any(dominates(pts[j], p, OBJS) for j in front), (i, p)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_single_objective_degenerates_to_argmax(data):
    """With one objective the front is exactly the argmax set (argmin for
    direction 'min'), and argbest picks its first member."""
    pts = _points(data)
    for key, direction in (("x", "max"), ("y", "min")):
        vals = [p[key] for p in pts]
        best = max(vals) if direction == "max" else min(vals)
        expect = [i for i, v in enumerate(vals) if v == best]
        assert pareto_front(pts, ((key, direction),)) == expect
        assert argbest(pts, key, direction) == expect[0]
