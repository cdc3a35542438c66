"""Kernel descriptors — Tally's non-intrusive interception boundary.

On NVIDIA GPUs Tally intercepts *device code* (PTX) at registration time and
rewrites it. The JAX/TPU analog of PTX is the Pallas launch descriptor: the
tile body + grid + BlockSpecs. Models emit ``KernelDescriptor``s for their
hot kernels (``repro.kernels``); Tally's transformation passes
(``core.transforms``) consume descriptors only — never user model code.

Contract mirroring the GPU programming model (paper §2): grid cells along
``parallel`axes`` are independent and may execute in any order (the
thread-block independence guarantee Tally relies on); axes not listed are
*sequential* (the Pallas "arbitrary" semantics — the analog of inter-block
dependencies in CUDA cooperative groups, see paper §6), and Tally never
reorders or splits them.

The descriptor body signature is ``body(pids, *refs)`` where ``pids`` is the
tuple of grid indices. Bodies must index through ``pids`` — never
``pl.program_id`` — so the transformation passes can re-bind block indices
(the ``blockIdx`` rewrite of the paper, done at the descriptor level).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np
from jax.experimental import pallas as pl


@dataclass(frozen=True)
class BlockMap:
    """One operand's blocking: block shape + block index map.

    ``index_map(pids) -> block indices`` (units of blocks, as in
    ``pl.BlockSpec``). Kept as a plain dataclass (not pl.BlockSpec) so
    transforms can wrap/rebind it and so the persistent form can derive
    manual ``pl.ds`` views from it.
    """

    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]

    def spec(self, pid_xform: Optional[Callable] = None) -> pl.BlockSpec:
        f = self.index_map
        if pid_xform is None:
            return pl.BlockSpec(self.block_shape, f)
        return pl.BlockSpec(self.block_shape,
                            lambda *pids: f(*pid_xform(pids)))


@dataclass(frozen=True)
class KernelDescriptor:
    """A Tally-schedulable kernel launch (the PTX analog)."""

    name: str
    body: Callable                      # body(pids, *in_refs, *out_refs, *scratch)
    grid: Tuple[int, ...]
    in_maps: Tuple[BlockMap, ...]
    out_maps: Tuple[BlockMap, ...]
    out_shape: Tuple[jax.ShapeDtypeStruct, ...]
    parallel_axes: Tuple[int, ...]      # grid axes with independent blocks
    scratch_shapes: Tuple[Any, ...] = ()
    flops: float = 0.0                  # per full launch (device model input)
    bytes_accessed: float = 0.0
    interpret: Optional[bool] = None    # None: decided by resolve_interpret
    revisits_output: bool = False       # sequential axis accumulates into out

    # -- derived -------------------------------------------------------------
    @property
    def sequential_axes(self) -> Tuple[int, ...]:
        return tuple(i for i in range(len(self.grid))
                     if i not in self.parallel_axes)

    @property
    def num_blocks(self) -> int:
        """Schedulable work units = product over parallel axes."""
        n = 1
        for ax in self.parallel_axes:
            n *= self.grid[ax]
        return int(n)

    @property
    def total_grid(self) -> int:
        return int(np.prod(self.grid))

    def block_work(self) -> Tuple[float, float]:
        """(flops, bytes) per schedulable block — the turnaround unit."""
        n = max(self.num_blocks, 1)
        return self.flops / n, self.bytes_accessed / n

    def replace(self, **kw) -> "KernelDescriptor":
        return dataclasses.replace(self, **kw)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode.

    The one place that decides it: an explicit flag wins (tests); ``None``
    means compiled on a TPU backend and interpreted on the CPU. Any other
    backend raises rather than silently interpreting on it.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas path for backend {backend!r}; pass "
                       "interpret= explicitly")


def pick_block(dim: int, target: int, align: int) -> int:
    """Block size along one dim: the whole dim when it fits ``target``,
    else the largest multiple of ``align`` that divides ``dim`` and is
    <= ``target``, else the whole dim. A TPU block's last two dims must be
    multiples of (8, 128) or span the array, so an unaligned divisor is
    never picked."""
    if dim <= target:
        return dim
    b = (target // align) * align
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim


def build_plain(desc: KernelDescriptor) -> Callable:
    """Compile the descriptor as an ordinary pallas_call (no transform)."""

    def kernel(*refs):
        pids = tuple(pl.program_id(i) for i in range(len(desc.grid)))
        desc.body(pids, *refs)

    return pl.pallas_call(
        kernel,
        grid=desc.grid,
        in_specs=[m.spec() for m in desc.in_maps],
        out_specs=[m.spec() for m in desc.out_maps],
        out_shape=list(desc.out_shape),
        scratch_shapes=list(desc.scratch_shapes),
        interpret=resolve_interpret(desc.interpret),
    )


# ---------------------------------------------------------------------------
# Launch record — what a client actually submits to the Tally server
# ---------------------------------------------------------------------------


@dataclass
class KernelLaunch:
    """One kernel launch request (descriptor + operands)."""

    desc: KernelDescriptor
    args: Tuple[Any, ...]
    # filled by the server:
    outputs: Any = None

    @property
    def work_key(self) -> Tuple:
        """Profiler cache key: kernel identity + work dimensions (paper
        profiles each unique (block dim, grid dim) configuration)."""
        return (self.desc.name, self.desc.grid,
                tuple(m.block_shape for m in self.desc.in_maps))
