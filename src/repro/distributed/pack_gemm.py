"""Pack-level sharded GEMM — the paper's three-level scaling, made real.

GAMA evaluates GEMM at three levels: a single AIE kernel, a *pack* of
engines chained over K with staggered placement (Figs. 3/4/7), and the
full 8x50 array.  This module is the pack and array levels for the TPU
re-targeting:

* **pack level** (:func:`pack_gemm`): a 2D ``(P, Q)`` pack grid laid over
  the mesh's model axis (``P * Q == |model|``).  ``P`` shards K — the
  cascade direction — and ``Q`` shards N.  A/B are placed
  *block-cyclically* over the P cascade positions (:func:`block_cyclic_index`)
  so padded tail blocks spread across engines instead of landing on the
  last one; each device runs a local Pallas GEMM (through
  :func:`repro.kernels.ops.matmul`, so the tuner's tile configs apply),
  and partial sums combine with a **staggered ring reduce**
  (:func:`staggered_ring_all_reduce`): each pack column starts its ring
  schedule at a stagger-shifted chunk, the collective-permute analogue of
  the paper's congestion-avoiding staggered kernel placement (Fig. 7).
* **overlapped dataflow** (``overlap=True``): the K-streamed schedule.
  The cascade in Figs. 3/7 streams partial sums between engines *while*
  the next K block is computing; here the local GEMM is split into its
  ``cyc`` block-cyclic K chunks, and each chunk's staggered ring
  **reduce-scatter** is emitted interleaved with the *next* chunk's
  matmul — data-independent, so the collective drains while the MXU is
  busy — followed by one terminal all-gather.  ``reduce="ring"`` /
  ``"psum"`` with ``overlap=False`` stay available as the unoverlapped
  baselines for A/B benchmarking (``benchmarks/run.py --level pack
  --reduce {ring,psum,overlap}``).
* **array level** (:func:`array_gemm`): composes packs across the data
  axis — M shards over ``data``, every data row runs the pack dataflow
  over ``model`` — one ``shard_map`` over the full mesh, the collective
  matmul the complete array executes.

Sharding mechanics: the model axis is split into ``(packq, packp)``
sub-axes of a derived mesh (:func:`split_pack_mesh`), so A is passed to
``shard_map`` as a **q-free** ``(d, p, Md, cyc*kb)`` tensor and the
in_spec replicates it across pack columns on device — no host-side
Q-fold materialization.

Dispatch: :func:`set_pack_context` installs a process-level context;
``ops.matmul`` (and therefore every model GEMM) routes through
:func:`pack_gemm` when the problem clears the context's FLOP threshold.
Pack-grid shape, stagger offset, reduce order and overlap default to the
tuning cache via ``repro.tuning.dispatch.pack_config`` (schema v3).

Numerics match :func:`repro.kernels.ref.ref_gemm` for float (dtype
tolerance; the ring and the K-streamed schedule change the summation
order) and exactly for int8 (int32 partial sums are associative;
requantization happens once, after the full reduction).  The result is
invariant to ``stagger`` and to ``overlap`` on/off — both only reorder
associative accumulations.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ref

__all__ = [
    "PackContext", "set_pack_context", "get_pack_context",
    "clear_pack_context", "pack_context", "pack_coords",
    "block_cyclic_index", "split_pack_mesh", "stage_a_blocks",
    "stage_b_blocks", "staggered_ring_all_reduce", "pack_gemm",
    "array_gemm",
]

# Names of the derived sub-axes the model axis is split into.
_Q_AXIS, _P_AXIS = "packq", "packp"


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# ---------------------------------------------------------------------------
# Pack geometry
# ---------------------------------------------------------------------------


def pack_coords(w: int, p: int):
    """Map model-axis device m to its (column q_i, cascade position j).

    Device numbering follows cascade.py: ``m = q_i * p + j`` — the P
    members of one pack column are contiguous on the axis.

    >>> pack_coords(8, 2)
    [(0, 0), (0, 1), (1, 0), (1, 1), (2, 0), (2, 1), (3, 0), (3, 1)]
    """
    return [(m // p, m % p) for m in range(w)]


def block_cyclic_index(p: int, cycles: int) -> np.ndarray:
    """K-block ownership: row j lists the blocks cascade position j holds.

    Block b goes to position ``b % p`` — cyclic, so when K does not
    divide evenly the zero-padded tail blocks spread across positions
    instead of piling onto the last one.

    >>> block_cyclic_index(2, 2).tolist()
    [[0, 2], [1, 3]]
    >>> block_cyclic_index(4, 1).tolist()
    [[0], [1], [2], [3]]
    """
    return np.arange(p * cycles).reshape(cycles, p).T


def split_pack_mesh(mesh: Mesh, model_axis: str, p: int, q: int) -> Mesh:
    """Derive a mesh whose model axis is split into (packq, packp).

    Device (qi * p + j) on the model axis becomes device (qi, j) on the
    sub-axes — the same numbering :func:`pack_coords` uses — so one
    PartitionSpec entry can shard over cascade positions while
    *replicating* over pack columns (the q-free A placement).  All other
    axes keep their names and order (model moves last).
    """
    names = list(mesh.axis_names)
    keep = [n for n in names if n != model_axis]
    assert _Q_AXIS not in keep and _P_AXIS not in keep, names
    dev = np.moveaxis(np.asarray(mesh.devices), names.index(model_axis), -1)
    dev = dev.reshape(dev.shape[:-1] + (q, p))
    return Mesh(dev, tuple(keep) + (_Q_AXIS, _P_AXIS))


def stage_a_blocks(ap: jax.Array, d: int, p: int, cyc: int,
                   kb: int) -> jax.Array:
    """Host-side A staging: (Mp, Kp) -> (d, p, Md, cyc*kb), **q-free**.

    Row block di and the block-cyclic K blocks of cascade position j land
    at [di, j]; replication across the Q pack columns happens on device
    via the shard_map in_spec (never materialized host-side).
    """
    mp, kp = ap.shape
    md = mp // d
    bc = block_cyclic_index(p, cyc)
    a4 = ap.reshape(d, md, p * cyc, kb)
    sel = a4[:, :, bc.reshape(-1), :].reshape(d, md, p, cyc, kb)
    return sel.transpose(0, 2, 1, 3, 4).reshape(d, p, md, cyc * kb)


def stage_b_blocks(bp: jax.Array, p: int, q: int, cyc: int,
                   kb: int) -> jax.Array:
    """Host-side B staging: (Kp, Np) -> (q, p, cyc*kb, nq).

    Pack column qi, cascade position j gets N column qi and the
    block-cyclic K blocks of position j (replicated over the data axis
    by the in_spec).
    """
    kp, np_ = bp.shape
    nq = np_ // q
    bc = block_cyclic_index(p, cyc)
    b4 = bp.reshape(p * cyc, kb, q, nq)
    sel = b4[bc.reshape(-1)].reshape(p, cyc, kb, q, nq)
    return sel.transpose(3, 0, 1, 2, 4).reshape(q, p, cyc * kb, nq)


# ---------------------------------------------------------------------------
# Staggered ring reduce
# ---------------------------------------------------------------------------


def _chunk_take(arr: jax.Array, c, rows: int, p: int) -> jax.Array:
    """Row-slot c (mod p) of an array chunked into p row groups."""
    return jax.lax.dynamic_slice_in_dim(arr, (c % p) * rows, rows, 0)


def _chunk_put(arr: jax.Array, c, val: jax.Array, rows: int,
               p: int) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(arr, val, (c % p) * rows, 0)


def _ring_reduce_scatter(x: jax.Array, axis_name: str, p: int, perm,
                         j, off) -> jax.Array:
    """p-1 ring steps; afterwards slot (j+1+off) holds this device's
    fully-reduced chunk.  The other slots hold partial sums the
    all-gather never reads (it only reads owned-or-received slots)."""
    rows = x.shape[0] // p
    acc = x
    # After step t, chunk (j-1-t) holds t+2 contributions; after p-1
    # steps device j owns the fully-reduced chunk (j+1+off).
    for t in range(p - 1):
        recv = jax.lax.ppermute(_chunk_take(acc, j - t + off, rows, p),
                                axis_name, perm)
        tgt = j - 1 - t + off
        acc = _chunk_put(acc, tgt, _chunk_take(acc, tgt, rows, p) + recv,
                         rows, p)
    return acc


def _ring_all_gather(acc: jax.Array, axis_name: str, p: int, perm,
                     j, off) -> jax.Array:
    """p-1 ring steps circulating the completed chunks."""
    rows = acc.shape[0] // p
    for t in range(p - 1):
        recv = jax.lax.ppermute(
            _chunk_take(acc, j + 1 - t + off, rows, p), axis_name, perm)
        acc = _chunk_put(acc, j - t + off, recv, rows, p)
    return acc


def staggered_ring_all_reduce(x: jax.Array, axis_name: str, p: int,
                              perm, stagger: int,
                              col_axis: Optional[str] = None) -> jax.Array:
    """Ring all-reduce over each P-subgroup with a per-column stagger.

    ``x``: the local partial, chunked into ``p`` pieces along axis 0.
    When ``col_axis`` is given, ``axis_name`` is a pure cascade axis of
    size p (the split-mesh layout) and the stagger column index comes
    from ``col_axis``; otherwise ``axis_name`` is the flat model axis
    and ``perm`` must be the disjoint union of subgroup rings (device
    ``qi*p + j`` sends to ``qi*p + (j+1) % p``).  Column ``qi`` starts
    its schedule at chunk offset ``qi * stagger`` — at any step,
    staggered columns move *different* chunk indices, the schedule-level
    stand-in for the paper's staggered kernel placement (every column
    shares links on a real torus; shifting the schedule avoids all
    columns hammering the same buffer slot at once).  The offset only
    relabels chunks within a ring, so the reduced value is unchanged.

    Runs inside ``shard_map``; the 2*(p-1) steps are the standard
    reduce-scatter + all-gather rings.
    """
    idx = jax.lax.axis_index(axis_name)
    if col_axis is None:
        j = idx % p
        off = (idx // p) * stagger
    else:
        j = idx
        off = jax.lax.axis_index(col_axis) * stagger
    acc = _ring_reduce_scatter(x, axis_name, p, perm, j, off)
    return _ring_all_gather(acc, axis_name, p, perm, j, off)


# ---------------------------------------------------------------------------
# Pack / array GEMM
# ---------------------------------------------------------------------------


def pack_gemm(a: jax.Array, b: jax.Array, mesh: Mesh, *,
              p: Optional[int] = None, q: Optional[int] = None,
              stagger: Optional[int] = None, reduce: Optional[str] = None,
              overlap: Optional[bool] = None, cycles: int = 2,
              model_axis: str = "model", data_axis: Optional[str] = None,
              out_dtype=None, scale: float = 1.0,
              mode: str = "auto") -> jax.Array:
    """C = a @ b over a (P, Q) pack grid on the mesh's model axis.

    a: (M, K); b: (K, N).  ``p`` shards K block-cyclically (the cascade),
    ``q`` shards N; ``p * q`` must equal the model-axis size.  When
    ``data_axis`` is given, M additionally shards across it (the array
    level — see :func:`array_gemm`).  Unspecified grid parameters come
    from the tuning cache (``dispatch.pack_config``), falling back to the
    planner's analytic KCE sweep.

    ``reduce``: ``"ring"`` — the staggered ring schedule; ``"psum"`` —
    XLA's subgroup psum (the unstaggered baseline); ``"overlap"`` —
    shorthand for ``reduce="ring", overlap=True``.  ``overlap=True``
    selects the K-streamed schedule: the local GEMM runs chunk by chunk
    and each chunk's ring reduce-scatter is interleaved with the next
    chunk's matmul (one terminal all-gather drains the ring); it
    requires the ring schedule and is a no-op at ``p == 1``.  ``mode``
    selects the *local* GEMM backend exactly like ``ops.matmul``
    (``"auto"`` = Pallas on TPU, jnp reference elsewhere).

    Non-divisible M/N/K are zero-padded and sliced; int8 inputs
    accumulate in int32 across the whole pack and requantize once at the
    end, matching ``ref.ref_gemm`` bit-for-bit.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    w = mesh.shape[model_axis]
    d = mesh.shape[data_axis] if data_axis else 1

    if reduce == "overlap":           # the bench flag's spelling
        reduce = "ring"
        overlap = True if overlap is None else overlap
    explicit_overlap = overlap
    if overlap and reduce is None:
        # An explicit overlap request pins the ring schedule family —
        # never let a cached psum pick turn it into an error.
        reduce = "ring"
    if overlap is None and reduce == "psum":
        overlap = False               # psum has no ring to stream
    if p is None or q is None or stagger is None or reduce is None \
            or overlap is None:
        from repro.tuning import dispatch
        cand = dispatch.pack_config(m, k, n, a.dtype, data_axis=d,
                                    model_axis=w)
        p = cand.p if p is None else p
        q = cand.q if q is None else q
        stagger = cand.stagger if stagger is None else stagger
        reduce = cand.reduce if reduce is None else reduce
        if overlap is None:
            # The tuner's overlap bit describes its own ring pick; an
            # explicitly-requested ring baseline keeps the tuned bit.
            overlap = cand.overlap if reduce == "ring" else False
    if p == 1:
        overlap = False               # nothing to stream at depth 1
    assert p * q == w, f"pack grid {p}x{q} != model axis {w}"
    assert reduce in ("ring", "psum"), reduce
    if overlap and reduce == "psum":
        raise ValueError("overlap streams the ring schedule; "
                         "reduce='psum' cannot overlap "
                         f"(explicit overlap={explicit_overlap})")

    integer = jnp.issubdtype(a.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int32 if integer else a.dtype
    out_dtype = jnp.dtype(out_dtype)

    cyc = cycles if k >= p * cycles else 1
    mp = _round_up(max(m, 1), d * p)
    kp = _round_up(max(k, 1), p * cyc)
    np_ = _round_up(max(n, 1), q)
    kb = kp // (p * cyc)
    nq = np_ // q

    ap = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    bp = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    ag = stage_a_blocks(ap, d, p, cyc, kb)    # (d, p, Md, cyc*kb) q-free
    bg = stage_b_blocks(bp, p, q, cyc, kb)    # (q, p, cyc*kb, nq)

    sub = split_pack_mesh(mesh, model_axis, p, q)
    da = data_axis if data_axis else None
    # (d, q, p, Md, nq)
    out = _pack_shard_map(ag, bg, sub=sub, da=da, p=p, cyc=cyc, kb=kb,
                          stagger=stagger, reduce=reduce, overlap=overlap,
                          acc_dtype=acc_dtype, mode=mode)
    # Every member of a column holds the full reduction; keep j == 0.
    out = out[:, :, 0]                                 # (d, q, Md, nq)
    out = out.transpose(0, 2, 1, 3).reshape(mp, np_)[:m, :n]
    # Requantize exactly once, after the full cross-device reduction.
    return ref.requantize(out, out_dtype, scale)


@functools.partial(jax.jit, static_argnames=(
    "sub", "da", "p", "cyc", "kb", "stagger", "reduce", "overlap",
    "acc_dtype", "mode"))
def _pack_shard_map(ag: jax.Array, bg: jax.Array, *, sub: Mesh,
                    da: Optional[str], p: int, cyc: int, kb: int,
                    stagger: int, reduce: str, overlap: bool, acc_dtype,
                    mode: str) -> jax.Array:
    """The per-device pack dataflow as one compiled program per static
    configuration.  Jitting matters off the traced path too: an eager
    ``shard_map`` dispatches the ring's slices, permutes and updates one
    primitive at a time, which costs seconds per call on host devices;
    compiled, a repeat call is a cache hit."""
    perm = [(j, (j + 1) % p) for j in range(p)]

    def local(a_l, b_l):
        al, bl = a_l[0, 0], b_l[0, 0]      # (Md, cyc*kb), (cyc*kb, nq)
        if p == 1:
            red = _local_matmul(al, bl, acc_dtype, mode)
        elif overlap:
            jdx = jax.lax.axis_index(_P_AXIS)
            off = jax.lax.axis_index(_Q_AXIS) * stagger
            rows = al.shape[0] // p

            def band(slot):
                # One output row band; its K chunks stream block-
                # cyclically through the local matmul, one chunk step
                # at a time.
                r = _chunk_take(al, slot, rows, p)
                out = _local_matmul(r[:, :kb], bl[:kb], acc_dtype, mode)
                for c in range(1, cyc):
                    out = out + _local_matmul(r[:, c * kb:(c + 1) * kb],
                                              bl[c * kb:(c + 1) * kb],
                                              acc_dtype, mode)
                return out

            # K-streamed pipelined ring: bands are computed just in
            # time, chunk by chunk, and each ring step's ppermute is
            # emitted adjacent to the *next* band's chunk matmuls —
            # data-independent, so the collective drains while the MXU
            # is busy (Figs. 3/7) at exactly the sequential ring's
            # 2*(p-1) message cost (no extra traffic to hide).
            acc = jnp.zeros((al.shape[0], bl.shape[1]), acc_dtype)
            acc = _chunk_put(acc, jdx + off, band(jdx + off), rows, p)
            nxt = band(jdx - 1 + off)
            for t in range(p - 1):
                recv = jax.lax.ppermute(
                    _chunk_take(acc, jdx - t + off, rows, p),
                    _P_AXIS, perm)
                cur = nxt
                if t + 1 < p - 1:
                    nxt = band(jdx - 2 - t + off)
                acc = _chunk_put(acc, jdx - 1 - t + off, cur + recv,
                                 rows, p)
            red = _ring_all_gather(acc, _P_AXIS, p, perm, jdx, off)
        elif reduce == "psum":
            red = jax.lax.psum(_local_matmul(al, bl, acc_dtype, mode),
                               _P_AXIS)
        else:
            partial = _local_matmul(al, bl, acc_dtype, mode)
            red = staggered_ring_all_reduce(partial, _P_AXIS, p, perm,
                                            stagger, col_axis=_Q_AXIS)
        return red[None, None, None]

    fn = jax.shard_map(local, mesh=sub,
                       in_specs=(P(da, _P_AXIS, None, None),
                                 P(_Q_AXIS, _P_AXIS, None, None)),
                       out_specs=P(da, _Q_AXIS, _P_AXIS, None, None),
                       check_vma=False)
    return fn(ag, bg)


def array_gemm(a: jax.Array, b: jax.Array, mesh: Mesh, *,
               data_axis: str = "data", **kwargs) -> jax.Array:
    """Full-mesh collective matmul: packs composed across the data axis.

    M shards over ``data_axis``; within each data row the (P, Q) pack
    dataflow runs over the model axis — the complete-array level of the
    paper's evaluation.  Accepts every :func:`pack_gemm` keyword.
    """
    return pack_gemm(a, b, mesh, data_axis=data_axis, **kwargs)


def _local_matmul(a_l: jax.Array, b_l: jax.Array, acc_dtype,
                  mode: str) -> jax.Array:
    """Per-device GEMM in the accumulation dtype (no requant — that
    happens once, after the cross-device reduction)."""
    from repro.kernels import ops
    return ops.matmul(a_l, b_l, out_dtype=acc_dtype, mode=mode,
                      allow_pack=False)


# ---------------------------------------------------------------------------
# Process-level dispatch context (consulted by kernels/ops.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackContext:
    """Routes large GEMMs through :func:`pack_gemm`.

    ``min_flops`` is the dispatch threshold on ``2*M*K*N`` — below it a
    single kernel wins (collective latency dominates), mirroring the
    paper's observation that packs only pay off once the problem covers
    the array.
    """

    mesh: Mesh
    model_axis: str = "model"
    data_axis: Optional[str] = None
    min_flops: float = 2.0 * 1024 ** 3

    def eligible(self, m: int, k: int, n: int) -> bool:
        return 2.0 * m * k * n >= self.min_flops


_CONTEXT: Optional[PackContext] = None


def set_pack_context(mesh: Mesh, *, model_axis: str = "model",
                     data_axis: Optional[str] = None,
                     min_flops: float = 2.0 * 1024 ** 3) -> PackContext:
    """Install the process-level pack context; returns it."""
    global _CONTEXT
    _CONTEXT = PackContext(mesh=mesh, model_axis=model_axis,
                           data_axis=data_axis, min_flops=min_flops)
    return _CONTEXT


def get_pack_context() -> Optional[PackContext]:
    return _CONTEXT


def clear_pack_context() -> None:
    global _CONTEXT
    _CONTEXT = None


@contextlib.contextmanager
def pack_context(mesh: Mesh, **kwargs):
    """Scoped :func:`set_pack_context` (tests, benchmarks)."""
    global _CONTEXT
    prev = _CONTEXT
    set_pack_context(mesh, **kwargs)
    try:
        yield _CONTEXT
    finally:
        _CONTEXT = prev
