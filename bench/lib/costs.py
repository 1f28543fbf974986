"""Operations and bytes the served work needs, from shapes alone.

The GEMM terms follow the program's ``obs/efficiency.model_flops_per_token``
and ``kernels/ops.op_cost_model`` (2·M·K·N operations; A, B and C each
through memory once).  Which GEMMs a forward runs, and what attention
costs, are the architecture's terms (:mod:`bench.lib.model`), reached
through the shape.  Only the work a step needs is counted: the rows of
tokens it serves, not the rows or lanes the program pads to, and the lm
head of a prompt chunk only for the row whose logits are used.

:func:`window_work` rebuilds, from a run's records, every forward call
the window made: each decode step with its live contexts, and each
prompt chunk.  The engine advances every prefilling slot one chunk per
step (its unbudgeted setting), so a prompt's chunks ran on the steps
leading up to its first token.
"""

from __future__ import annotations

import dataclasses
from typing import List

from bench.lib.model import arch_of

BF16 = 2


def gemm_flops(shapes) -> float:
    return sum(2.0 * m * k * n for m, k, n in shapes)


def gemm_least_s(shapes, peak_flops: float, bw: float,
                 nbytes: int = BF16) -> float:
    """Least time of a set of GEMM calls: each call bound by its
    operations at the peak or its bytes at the bandwidth."""
    return sum(max(2.0 * m * k * n / peak_flops,
                   (m * k + k * n + m * n) * nbytes / bw)
               for m, k, n in shapes)


def causal_keys(lo: int, take: int) -> int:
    """Keys attended by the queries at positions lo .. lo+take-1."""
    return take * lo + take * (take + 1) // 2


@dataclasses.dataclass
class Call:
    step: int
    kind: str              # "chunk" | "decode"
    rows: int              # token rows served
    head_rows: int
    keys: int              # attention keys over all rows
    contexts: List[int]    # decode: live context per slot


def window_work(run, chunk: int) -> List[Call]:
    """Every forward call of the window's steps (those that began
    before the close)."""
    steps = {st.index for st in run.steps if st.t0 < run.seconds}
    if not steps:
        return []
    lo_step, hi_step = min(steps), max(steps)
    calls: List[Call] = []
    by_index = {r.req.index: r for r in run.records}
    # Decodes: the k-th decode of a request serves context plen + k.
    done_before = {}
    for r in run.records:
        before = sum(1 for t in r.times if t < 0)
        done_before[r.req.index] = max(0, before - 1)
    count = dict(done_before)
    for st in sorted(run.steps, key=lambda x: x.index):
        if st.index not in steps or not st.decoded:
            continue
        ctx = []
        for i in st.decoded:
            count[i] += 1
            ctx.append(len(by_index[i].req.prompt) + count[i])
        calls.append(Call(st.index, "decode", len(ctx), len(ctx),
                          sum(ctx), ctx))
    # Chunks: backwards from the first token's step, or forwards from
    # the admission for a prompt still prefilling at the close.
    for r in run.records:
        plen = len(r.req.prompt)
        n = -(-plen // chunk)
        if r.first_step >= 0:
            first = r.first_step - (n - 1)
        elif r.admit_step >= 0:
            first = r.admit_step
        else:
            continue
        for k in range(n):
            step = first + k
            if not lo_step <= step <= hi_step:
                continue
            lo = k * chunk
            take = min(chunk, plen - lo)
            calls.append(Call(step, "chunk", take, int(k == n - 1),
                              causal_keys(lo, take), []))
    return calls


def model_flops(s, calls: List[Call]) -> float:
    arch = arch_of(s)
    return sum(gemm_flops(arch.forward_gemms(s, c.rows, c.head_rows))
               + arch.attention_flops(s, c.keys) for c in calls)
