"""Tally kernel transformation passes (paper §4.1), TPU-adapted.

Slicing
    Partition the blocks of a kernel along its *parallel* grid axes into K
    sub-launches. The paper rewrites ``blockIdx -> blockIdx + offset`` in
    PTX; here we re-bind the descriptor's block-index maps (and the ``pids``
    seen by the body) with a linear offset — the same semantics at the
    descriptor level, with user kernel code untouched.

Preemption (persistent-worker form)
    The paper rewrites kernels into Persistent-Thread-Block style: W worker
    blocks iterate over a global task counter, polling a preemption flag
    each iteration. TPU grid cells on a core run sequentially and have no
    cross-grid atomics, so the TPU-idiomatic equivalent is:
      - grid = (W,): W persistent workers,
      - *static round-robin* task assignment (task t belongs to worker
        t mod W) instead of a dynamic counter — deterministic, contention-
        free, and identical load balance for the uniform tiles of DL
        kernels,
      - a cooperative (start_task, budget) scalar pair instead of a
        mid-flight flag: each launch executes at most ``budget`` tasks per
        worker then writes a per-worker progress count. The scheduler
        preempts by bounding the budget and *resumes* from the progress
        watermark — same block-granularity turnaround bound as the paper's
        flag poll (the scheduler never waits more than one task per worker).

Unified synchronization (paper Fig. 3b)
    CUDA needs it because threads of a block may reach ``__syncthreads``/
    ``return`` divergently once the PTB loop is added. Pallas/TPU has no
    intra-block thread divergence (vector predication instead of thread
    branches); the pass's *purpose* — make the persistent wrapper safe for
    arbitrary bodies — is met by predicating the whole tile body with
    ``lax.cond(active, body, noop)``, which is legal for any body including
    ones with internal ``lax`` control flow.

Sequential axes (K-accumulation, chunk recurrences) are never split: a
"task" is one combination of parallel-axis indices; the body runs its full
sequential sweep inside the task (the cluster-level fallback of paper §6).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.descriptor import (BlockMap, KernelDescriptor,
                                   resolve_interpret)


# ---------------------------------------------------------------------------
# Slicing transformation
# ---------------------------------------------------------------------------


def _slice_axis(desc: KernelDescriptor) -> int:
    """Slice along the largest parallel axis (most scheduling freedom)."""
    if not desc.parallel_axes:
        raise ValueError(f"{desc.name}: no parallel axes — not sliceable "
                         "(cooperative-kernel fallback, paper §6)")
    return max(desc.parallel_axes, key=lambda ax: desc.grid[ax])


def slice_plan(desc: KernelDescriptor, num_slices: int
               ) -> List[Tuple[int, int]]:
    """[(offset, length)] covering the sliced axis in num_slices pieces."""
    ax = _slice_axis(desc)
    n = desc.grid[ax]
    k = max(1, min(num_slices, n))
    bounds = [round(i * n / k) for i in range(k + 1)]
    return [(bounds[i], bounds[i + 1] - bounds[i]) for i in range(k)
            if bounds[i + 1] > bounds[i]]


def make_slice(desc: KernelDescriptor, offset: int, length: int
               ) -> KernelDescriptor:
    """Sub-kernel covering blocks [offset, offset+length) of the slice axis.

    This is the paper's ``blockIdx + offset`` rewrite: the body still sees
    *original* block indices (offset re-added), so its task computation is
    unchanged; only the launch geometry shrinks.
    """
    ax = _slice_axis(desc)

    def shift(pids: Tuple) -> Tuple:
        return tuple(p + offset if i == ax else p
                     for i, p in enumerate(pids))

    def body(pids, *refs):
        desc.body(shift(pids), *refs)

    grid = tuple(length if i == ax else g for i, g in enumerate(desc.grid))
    return desc.replace(
        name=f"{desc.name}@slice[{offset}:{offset + length}]",
        body=body,
        grid=grid,
        in_maps=tuple(BlockMap(m.block_shape,
                               partial(_shifted_map, m.index_map, ax, offset))
                      for m in desc.in_maps),
        out_maps=tuple(BlockMap(m.block_shape,
                                partial(_shifted_map, m.index_map, ax, offset))
                       for m in desc.out_maps),
    )


def _shifted_map(f, ax, offset, *pids):
    return f(*(p + offset if i == ax else p for i, p in enumerate(pids)))


def build_sliced(desc: KernelDescriptor, offset: int, length: int) -> Callable:
    """Callable(prev_outputs, *args) -> outputs, writing only this slice.

    Outputs are threaded through via input/output aliasing so successive
    slice launches accumulate into one buffer (the GPU in-place semantics).
    """
    sub = make_slice(desc, offset, length)
    n_in = len(sub.in_maps)
    n_out = len(sub.out_maps)

    def kernel(*refs):
        pids = tuple(pl.program_id(i) for i in range(len(sub.grid)))
        # refs = in_refs + prev_out_refs + out_refs + scratch; drop prev views
        ins = refs[:n_in]
        outs = refs[n_in + n_out:]
        sub.body(pids, *ins, *outs)

    call = pl.pallas_call(
        kernel,
        grid=sub.grid,
        in_specs=[m.spec() for m in sub.in_maps]
        + [m.spec() for m in sub.out_maps],          # prev outputs (aliased)
        out_specs=[m.spec() for m in sub.out_maps],
        out_shape=list(sub.out_shape),
        scratch_shapes=list(sub.scratch_shapes),
        input_output_aliases={n_in + i: i for i in range(n_out)},
        interpret=resolve_interpret(sub.interpret),
    )

    def run(prev_outputs, *args):
        prev = (list(prev_outputs) if isinstance(prev_outputs, (list, tuple))
                else [prev_outputs])
        return call(*args, *prev)

    return run


# ---------------------------------------------------------------------------
# Preemption transformation (persistent-worker form)
# ---------------------------------------------------------------------------

# VMEM of one TPU v5e TensorCore: the persistent-worker form maps whole
# operands into it, so a larger launch is refused before compiling
VMEM_BYTES = 128 * 2 ** 20


def _parallel_dims(desc: KernelDescriptor) -> Tuple[int, ...]:
    return tuple(desc.grid[ax] for ax in desc.parallel_axes)


def _task_to_pids(desc: KernelDescriptor, task, seq_pids: Tuple):
    """Reconstruct full grid indices from the flat task index (the paper's
    'workers use the task index to reconstruct block indices')."""
    dims = _parallel_dims(desc)
    pids = [None] * len(desc.grid)
    rem = task
    for ax, d in zip(reversed(desc.parallel_axes), reversed(dims)):
        pids[ax] = rem % d
        rem = rem // d
    it = iter(seq_pids)
    for ax in desc.sequential_axes:
        pids[ax] = next(it)
    return tuple(pids)


def preempt_watermark(start: int, budget: int, num_workers: int,
                      total: int) -> int:
    """Progress after a budgeted launch: with static round-robin, worker w
    completes its first min(budget, remaining) tasks >= start of residue
    class w, so tasks [start, start + budget*W) are exactly the completed
    window (capped at total). This is the host-side resume point — the
    deterministic analog of the paper's global task counter."""
    return min(start + budget * num_workers, total)


def make_preemptible(desc: KernelDescriptor, num_workers: int) -> Callable:
    """Build the persistent-worker form of a kernel.

    Returns ``run(prev_outputs, start_task, budget, *args) ->
    (outputs, per_worker_done)``. ``budget`` = max tasks per worker this
    launch (the cooperative preemption quantum; turnaround bound = one task
    per worker). Resume by relaunching with
    ``start_task = preempt_watermark(start, budget, W, total)``.
    """
    W = max(1, min(num_workers, desc.num_blocks))
    total = desc.num_blocks
    n_in = len(desc.in_maps)
    n_out = len(desc.out_maps)
    seq_dims = tuple(desc.grid[ax] for ax in desc.sequential_axes)
    n_seq = int(np.prod(seq_dims)) if seq_dims else 1

    def view(ref, bmap: BlockMap, pids):
        idx = bmap.index_map(*pids)
        # a block spanning its whole dim is a static full slice: the
        # compiler refuses dynamic offsets into an unaligned lane dim
        slices = tuple(slice(None) if s == full else pl.ds(b * s, s)
                       for b, s, full in zip(idx, bmap.block_shape,
                                             ref.shape))
        return ref.at[slices]

    def kernel(start_ref, budget_ref, *refs):
        w = pl.program_id(0)
        ins = refs[:n_in]
        outs = refs[n_in + n_out: n_in + 2 * n_out]
        prog_ref = refs[n_in + 2 * n_out]
        scratch = refs[n_in + 2 * n_out + 1:]
        start = start_ref[0]
        budget = budget_ref[0]

        def run_task(task):
            def seq_step(flat_seq, _):
                sp = []
                rem = flat_seq
                for d in reversed(seq_dims):
                    sp.append(rem % d)
                    rem = rem // d
                sp = tuple(reversed(sp))
                pids = _task_to_pids(desc, task, sp)
                in_views = [view(r, m, pids)
                            for r, m in zip(ins, desc.in_maps)]
                out_views = [view(r, m, pids)
                             for r, m in zip(outs, desc.out_maps)]
                desc.body(pids, *in_views, *out_views, *scratch)
                return 0

            jax.lax.fori_loop(0, n_seq, seq_step, 0)

        def step(t, done):
            task = start + t
            mine = (task % W) == w
            active = (task < total) & mine & (done < budget)
            # unified-synchronization analog: predicate the whole tile body
            jax.lax.cond(active, lambda: (run_task(task), None)[1],
                         lambda: None)
            return done + jnp.where(active, 1, 0)

        done = jax.lax.fori_loop(0, total, step, 0, unroll=False)
        prog_ref[w] = done

    def build(arg_avals):
        # whole operands live in VMEM for the launch (one copy each, no
        # pipelining); the scalars and the progress vector live in SMEM
        window = sum(_nbytes(a) for a in arg_avals) + 2 * sum(
            _nbytes(o) for o in desc.out_shape)
        if window > VMEM_BYTES:
            raise ValueError(
                f"{desc.name}: preemptible form maps whole operands into "
                f"VMEM ({window} bytes > {VMEM_BYTES})")
        whole = pl.BlockSpec(memory_space=pltpu.VMEM)
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        return pl.pallas_call(
            kernel,
            grid=(W,),
            in_specs=[smem, smem]                                # start, budget
            + [whole] * len(arg_avals)                           # full inputs
            + [whole] * n_out,                                   # prev outputs
            out_specs=[whole] * n_out + [smem],                  # + progress
            out_shape=list(desc.out_shape)
            + [jax.ShapeDtypeStruct((W,), jnp.int32)],
            scratch_shapes=list(desc.scratch_shapes),
            input_output_aliases={2 + len(arg_avals) + i: i
                                  for i in range(n_out)},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_BYTES),
            interpret=resolve_interpret(desc.interpret),
        )

    cache: dict = {}

    def run(prev_outputs, start_task, budget, *args):
        prev = (list(prev_outputs)
                if isinstance(prev_outputs, (list, tuple))
                else [prev_outputs])
        key = tuple((a.shape, str(a.dtype)) for a in args)
        if key not in cache:
            cache[key] = build([jax.ShapeDtypeStruct(a.shape, a.dtype)
                                for a in args])
        start = jnp.asarray([start_task], jnp.int32)
        bud = jnp.asarray([budget], jnp.int32)
        outs = cache[key](start, bud, *args, *prev)
        return outs[:-1], outs[-1]

    run.num_workers = W
    run.total_tasks = total
    run.watermark = lambda start, budget: preempt_watermark(
        start, budget, W, total)
    return run


def _nbytes(aval) -> int:
    return int(np.prod(aval.shape)) * jnp.dtype(aval.dtype).itemsize
