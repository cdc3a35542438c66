"""Tally transformation-pass correctness: sliced and preemptive forms must
reproduce the plain kernel exactly, for every kernel family, any slice
count / worker count / budget schedule (property-tested)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import transforms as T
from repro.core.descriptor import build_plain
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_desc
from repro.kernels.matmul import matmul_desc
from repro.kernels.mamba2_scan import mamba2_scan_desc

RNG = np.random.default_rng(7)


def _run_sliced(desc, args, num_slices):
    outs = [jnp.zeros(o.shape, o.dtype) for o in desc.out_shape]
    for off, ln in T.slice_plan(desc, num_slices):
        outs = list(T.build_sliced(desc, off, ln)(outs, *args))
    return outs


def _run_preemptible(desc, args, num_workers, budgets):
    """Run to completion with a (cycled) schedule of per-launch budgets."""
    pre = T.make_preemptible(desc, num_workers)
    outs = [jnp.zeros(o.shape, o.dtype) for o in desc.out_shape]
    start, i, n_launches = 0, 0, 0
    while start < pre.total_tasks:
        b = budgets[i % len(budgets)]
        outs, done = pre(outs, start, b, *args)
        new_start = pre.watermark(start, b)
        assert new_start > start
        start = new_start
        i += 1
        n_launches += 1
        assert n_launches < 10_000
    return outs


def _matmul_case():
    a = jnp.asarray(RNG.normal(size=(96, 256)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(256, 384)), jnp.float32)
    desc = matmul_desc(96, 256, 384, bm=16, bk=128, bn=128)   # grid (6,3,2)
    want = [ref.matmul_ref(a, b)]
    return desc, (a, b), want


def _flash_case():
    BH, S, D, G = 6, 32, 8, 2
    q = jnp.asarray(RNG.normal(size=(BH, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(BH // G, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(BH // G, S, D)), jnp.float32)
    desc = flash_attention_desc(BH, S, S, D, G, causal=True, bq=8, bk=8)
    want = [ref.attention_ref(q, k, v, causal=True, group=G)]
    return desc, (q, k, v), want


def _ssd_case():
    B, S, NH, HD, DS = 3, 24, 2, 4, 4
    x = jnp.asarray(RNG.normal(size=(B, S, NH, HD)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(B, S, NH)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(NH,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, DS)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, DS)), jnp.float32)
    Dp = jnp.asarray(RNG.normal(size=(NH,)), jnp.float32)
    desc = mamba2_scan_desc(B, S, NH, HD, DS, chunk=8)
    y, h = ref.ssd_ref(x, dt, A, Bm, Cm, Dp)
    return desc, (x, dt, A, Bm, Cm, Dp), [y, h]


CASES = {"matmul": _matmul_case, "flash": _flash_case, "ssd": _ssd_case}


@pytest.mark.parametrize("case", sorted(CASES))
def test_plain_matches_ref(case):
    desc, args, want = CASES[case]()
    outs = build_plain(desc)(*args)
    for o, w in zip(outs, want):
        np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("num_slices", [1, 2, 3, 7])
def test_sliced_matches_ref(case, num_slices):
    desc, args, want = CASES[case]()
    outs = _run_sliced(desc, args, num_slices)
    for o, w in zip(outs, want):
        np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("num_workers,budgets", [(1, [1]), (2, [1]),
                                                 (4, [2]), (3, [1, 2, 5])])
def test_preemptible_matches_ref(case, num_workers, budgets):
    desc, args, want = CASES[case]()
    outs = _run_preemptible(desc, args, num_workers, budgets)
    for o, w in zip(outs, want):
        np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_slice_plan_properties():
    desc, _, _ = _matmul_case()
    for k in range(1, 20):
        plan = T.slice_plan(desc, k)
        ax = max(desc.parallel_axes, key=lambda a: desc.grid[a])
        # covers exactly [0, grid[ax]) without overlap
        assert plan[0][0] == 0
        assert sum(ln for _, ln in plan) == desc.grid[ax]
        for (o1, l1), (o2, _) in zip(plan, plan[1:]):
            assert o1 + l1 == o2


@settings(max_examples=15, deadline=None)
@given(num_workers=st.integers(1, 8), budget=st.integers(1, 6),
       start_frac=st.floats(0.0, 1.0))
def test_watermark_monotone_and_bounded(num_workers, budget, start_frac):
    total = 24
    start = int(start_frac * (total - 1))
    wm = T.preempt_watermark(start, budget, num_workers, total)
    assert start < wm <= total


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), num_workers=st.integers(1, 6),
       budget=st.integers(1, 4))
def test_preemptible_matmul_property(seed, num_workers, budget):
    """Any (W, budget) schedule completes and matches the oracle."""
    r = np.random.default_rng(seed)
    a = jnp.asarray(r.normal(size=(32, 256)), jnp.float32)
    b = jnp.asarray(r.normal(size=(256, 512)), jnp.float32)
    desc = matmul_desc(32, 256, 512, bm=8, bk=128, bn=128)    # grid (4,4,2)
    outs = _run_preemptible(desc, (a, b), num_workers, [budget])
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-4)
