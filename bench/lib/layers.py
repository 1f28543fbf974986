"""Per-layer metrics: the context their readers get, and the readings
that several metric files share.

Each per-layer metric named in ``BENCHMARK.json`` is read by
``bench/metrics/<name>.py``, whose ``read(ctx)`` returns a number or
None.  None means the run had nothing for it to read, and the metric is
left out of the result line; a share of a roofline or a peak is never
reported as 0 for want of a reading.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import List, Optional

from bench.lib import costs
from bench.lib.model import arch_of, module
from bench.lib.peaks import peaks
from bench.lib.replay import percentile


@dataclasses.dataclass
class Context:
    run: object            # replay.Run of the window
    shape: object          # the architecture's shape (bench.lib.model)
    mix: dict
    trace: object          # xtrace.Reduced, the window's device trace
    device_kind: str

    @property
    def peaks(self) -> dict:
        return peaks(self.device_kind)

    @property
    def arch(self):
        """The architecture module, whose cost terms take the shape."""
        return arch_of(self.shape)

    def window_steps(self):
        return [st for st in self.run.steps if st.t0 < self.run.seconds]

    def calls(self) -> List[costs.Call]:
        return costs.window_work(self.run, self.mix["engine"]["prefill_chunk"])


def load_reader(root: Path, name: str):
    return module(root, "metrics", name).read


def read_all(root: Path, metrics: list, ctx: Context) -> dict:
    out = {}
    for m in metrics:
        value = load_reader(root, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- readings shared by several metric files ---------------------------


def device_idle_pct(ctx: Context) -> Optional[float]:
    t = ctx.trace
    if not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def mfu_pct(ctx: Context) -> Optional[float]:
    """Model operations the window's served tokens need, over the traced
    window at the chip's bf16 peak."""
    flops = costs.model_flops(ctx.shape, ctx.calls())
    if not flops or not ctx.trace.window_s:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks["bf16_flops"])


def kernel_roofline_pct(ctx: Context, kernel: str, least_s: float
                        ) -> Optional[float]:
    spent = ctx.trace.kernel_s(kernel)
    if not spent or not least_s:
        return None
    return 100.0 * least_s / spent


def gemm_roofline_pct(ctx: Context) -> Optional[float]:
    p = ctx.peaks
    least = sum(costs.gemm_least_s(
        ctx.arch.forward_gemms(ctx.shape, c.rows, c.head_rows),
        p["bf16_flops"], p["hbm_bytes_per_s"]) for c in ctx.calls())
    return kernel_roofline_pct(ctx, "gama_gemm", least)


def paged_decode_roofline_pct(ctx: Context) -> Optional[float]:
    p = ctx.peaks
    least = 0.0
    for c in ctx.calls():
        if c.kind == "decode":
            flops, nbytes = ctx.arch.paged_decode_cost(ctx.shape,
                                                       c.contexts)
            least += max(flops / p["bf16_flops"],
                         nbytes / p["hbm_bytes_per_s"])
    return kernel_roofline_pct(ctx, "gama_flash_paged_decode", least)


def step_p50_ms(ctx: Context) -> Optional[float]:
    return percentile([(st.t1 - st.t0) * 1e3 for st in ctx.window_steps()],
                      50)


def decode_batch_mean(ctx: Context) -> Optional[float]:
    sizes = [len(st.decoded) for st in ctx.window_steps() if st.decoded]
    return sum(sizes) / len(sizes) if sizes else None
