#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU: serve ``smollm_360m`` at its
published widths through ``ServeEngine`` and check the served logits.

    python chip_smoke.py                 # one chip (what CI-style runs use)
    python chip_smoke.py --four-chips    # the pack-sharded path, 4 chips

One chip, in one process:

1. builds ``smollm_360m`` FULL (32 layers, d_model 960, 15/5 heads of
   64, d_ff 2560, vocab 49152, bf16 compute) with weights from
   ``--seed``;
2. replays 8 requests (prompts of 192-384 tokens, 24-40 new tokens,
   staggered arrivals) through the launcher's ``run_trace`` on a
   4-slot engine with paged bf16 KV and 128-token prefill chunks, so
   chunked prefill and batched paged decode both run;
3. checks that the Pallas kernels served (``ops.matmul.kernel`` and
   ``ops.decode_paged.kernel`` counted, their ``.ref`` routes not);
4. recomputes every served logits row — the prompt's last position and
   each decode step — with the same weights through the
   ``kernels/ref.py`` oracles on the same chip, teacher-forced on the
   served tokens, in bf16 and in f32, and holds the served rows to the
   tolerances below.

``--four-chips`` runs only the pack-sharded path: the same model and
requests served with a (1, 4) pack mesh, so the lm-head and FFN GEMMs
run as ``pack_gemm`` over the four chips, against the same requests on
one chip without a mesh.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any
failed phase or tolerance miss exits non-zero without it, and so does a
run where jax finds no TPU (``JAX_PLATFORMS=cpu``).  The compile cache
goes to ``$JAX_COMPILATION_CACHE_DIR``, else to ``.cache/jax_compile``
in this checkout; kernel tiles come from the analytic prior only (an
empty tuning cache at ``.cache/tuning/chip_smoke.json``).

``--rehearse-on-cpu`` runs every phase on the CPU at the smoke preset
(cast to bf16, Pallas kernels in interpret mode) and then still fails
the device check: a rehearsal, never a pass.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent

# Tolerances on |served - oracle| over every compared logits row.  The
# served path and the bf16 oracle are two bf16 pipelines that round at
# the same points but accumulate in different orders, so each differs
# from the f32 oracle by about the bf16 noise of the forward pass, and
# from each other by at most about twice that.  NOISE_MEAN/NOISE_MAX
# scale the bf16 oracle's own distance from the f32 oracle (measured in
# the same run, on the same rows); the SLACK terms, in units of the f32
# logits' standard deviation, cover float32 rounding where that
# distance is ~0 (the f32 smoke preset).  A wrong page, position, mask
# or scale moves logits by O(1) standard deviations.
NOISE_MEAN = 2.0
NOISE_MAX = 4.0
SLACK_MEAN = 1e-4
SLACK_MAX = 1e-3

SLOTS = 4
REQUESTS = 8
PAGE_SIZE = 16
PREFILL_CHUNK = 128


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Sums backend compile seconds and persistent-cache hits/misses
    from jax's monitoring events (compile seconds include cache loads)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        # Programs that compile in under a second are not cached, so a
        # warm run still counts a few misses.
        state = ("warm" if self.hits else
                 "cold" if self.misses else "no persistent-cache use")
        return (f"compile_s={self.seconds:.3f} ({state}: "
                f"{self.hits} cache hits, {self.misses} misses)")


def make_trace(vocab: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(REQUESTS):
        plen = int(rng.integers(192, 385))
        trace.append({
            "id": i, "arrival": 3 * i, "max_new": int(rng.integers(24, 41)),
            "prompt": rng.integers(0, vocab, size=(plen,)).astype(np.int32)})
    return trace


def serve(cfg, params, trace, seed: int, pack_mesh=None):
    """Replay ``trace`` through a fresh engine; returns (report, taps)
    with taps[trace id] = [(position, logits row)] in serving order."""
    from repro.launch.serve import run_trace
    from repro.serving.engine import ServeConfig, ServeEngine
    max_len = max(len(t["prompt"]) + t["max_new"] for t in trace) + 8
    # --four-chips packs every GEMM at least as large as a decode
    # step's FFN projection: at decode that is the FFN and the lm head
    # (the qkv and out projections stay local), in a prefill chunk all.
    scfg = ServeConfig(batch_slots=SLOTS, max_len=max_len, seed=seed,
                       kv="paged", page_size=PAGE_SIZE,
                       prefill_chunk=PREFILL_CHUNK, pack_mesh=pack_mesh,
                       pack_min_flops=2.0 * SLOTS * cfg.d_model * cfg.d_ff)
    engine = ServeEngine(cfg, params, scfg)
    taps = {}
    # A fresh engine numbers requests from 0 in submission order, which
    # is trace order for a step-indexed replay: rid == trace id.
    engine.logits_tap = lambda rid, pos, row: taps.setdefault(
        rid, []).append((pos, row))
    try:
        rep = run_trace(engine, trace, log=None)
        packed = engine.packed_gemms
    finally:
        engine.close()
    if sorted(rep["results"]) != sorted(t["id"] for t in trace):
        fail(f"served {sorted(rep['results'])}, expected every request")
    if sorted(taps) != sorted(rep["results"]):
        fail("logits tap missed requests")
    for t in trace:
        got = rep["results"][t["id"]]
        if len(got) != t["max_new"] or len(taps[t["id"]]) != t["max_new"]:
            fail(f"request {t['id']}: {len(got)} tokens, "
                 f"{len(taps[t['id']])} logits rows, want {t['max_new']}")
    rep["packed_gemms"] = packed
    return rep, taps


def reference_logits(cfg, params, trace, results):
    """The whole model through the kernels/ref.py oracles, teacher-
    forced on prompt + served tokens: (bf16-compute, f32-compute)
    logits, each (requests, padded length, vocab)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import forward
    seqs = [np.concatenate([t["prompt"], results[t["id"]][:-1]])
            for t in trace]
    length = -(-max(len(s) for s in seqs) // 128) * 128
    toks = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s       # causal: the zero tail is never seen
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32",
                                cache_dtype="float32")
    out = []
    before = ops_counters()
    with ops.force_mode("ref"), jax.default_matmul_precision("highest"):
        for c in (cfg, cfg32):
            fn = jax.jit(lambda p, t, c=c: forward(p, {"tokens": t}, c)[0])
            out.append(fn(params, jnp.asarray(toks)))
    check_reference_routes(before, ops_counters())
    return out


def compare(name, taps, trace, ref16, ref32, other=None):
    """Hold every served row to the tolerances; returns the worst error.
    ``other`` = (taps, results, own results) of a second run compares
    the two runs directly, on the rows whose inputs both runs share:
    the prompt's row, then decode rows until the greedy tokens part."""
    import jax.numpy as jnp
    import numpy as np
    errs = {"mean": [], "max": [], "noise_mean": [], "noise_max": [],
            "scale": [], "rows": 0}
    for i, t in enumerate(trace):
        rows = taps[t["id"]]
        if other is not None:
            o_taps, o_res, res = other
            a, b = o_res[t["id"]], res[t["id"]]
            split = int(np.argmax(a != b)) if (a != b).any() else len(a)
            # Row at position plen-1+j is fed generated tokens < j.
            last = len(t["prompt"]) - 1 + split
            o = dict(o_taps[t["id"]])
            rows = [(p, r) for p, r in rows if p <= last]
            ref = jnp.stack([o[p] for p, _ in rows])
        pos = jnp.asarray([p for p, _ in rows])
        served = jnp.stack([r for _, r in rows])
        if other is None:
            ref = ref16[i, pos]
        r16, r32 = ref16[i, pos], ref32[i, pos]
        d = jnp.abs(served - ref)
        n = jnp.abs(r16 - r32)
        errs["mean"].append(float(d.mean()))
        errs["max"].append(float(d.max()))
        errs["noise_mean"].append(float(n.mean()))
        errs["noise_max"].append(float(n.max()))
        errs["scale"].append(float(r32.std(axis=-1).mean()))
        errs["rows"] += int(pos.shape[0])
        if not bool(jnp.isfinite(served).all()):
            fail(f"{name}: non-finite served logits (request {t['id']})")
    scale = float(np.mean(errs["scale"]))
    mean_err, max_err = float(np.mean(errs["mean"])), max(errs["max"])
    noise_mean = float(np.mean(errs["noise_mean"]))
    noise_max = max(errs["noise_max"])
    tol_mean = NOISE_MEAN * noise_mean + SLACK_MEAN * scale
    tol_max = NOISE_MAX * noise_max + SLACK_MAX * scale
    log(f"{name}: rows={errs['rows']} mean_abs_err={mean_err:.6g} "
        f"(tol {tol_mean:.6g}) max_abs_err={max_err:.6g} (tol "
        f"{tol_max:.6g}) | bf16-vs-f32 oracle noise mean={noise_mean:.6g} "
        f"max={noise_max:.6g} | logit std={scale:.6g}")
    if not (mean_err <= tol_mean and max_err <= tol_max):
        fail(f"{name}: logits outside tolerance")
    return max_err


def ops_counters():
    """Every ``ops.<op>.<route>`` counter: routes are counted as calls
    are traced."""
    from repro import obs
    c = obs.get_obs().registry.snapshot()["counters"]
    return {k: int(v) for k, v in c.items() if k.startswith("ops.")}


def route_counts():
    c = ops_counters()
    names = ("ops.matmul.kernel", "ops.matmul.ref", "ops.matmul.pack",
             "ops.decode_paged.kernel", "ops.decode_paged.ref")
    return {n: c.get(n, 0) for n in names}


def check_reference_routes(before, after) -> None:
    """The oracle pass traced the ``.ref`` routes only: no kernel or
    pack route was counted while it traced."""
    grew = sorted(k for k in after if after[k] != before.get(k, 0))
    log("reference routes: " + " ".join(
        f"{k}=+{after[k] - before.get(k, 0)}" for k in grew))
    if not any(k.endswith(".ref") for k in grew):
        fail("the reference pass traced no oracle")
    if any(not k.endswith(".ref") for k in grew):
        fail(f"the reference pass traced a non-oracle route: {grew}")


def check_routes(counts, packed: bool) -> None:
    log("routes: " + " ".join(f"{k}={v}" for k, v in counts.items()))
    if counts["ops.matmul.kernel"] <= 0 \
            or counts["ops.decode_paged.kernel"] <= 0:
        fail("the Pallas kernels did not serve")
    if counts["ops.matmul.ref"] or counts["ops.decode_paged.ref"]:
        fail("the served path fell back to the jnp oracles")
    if packed != (counts["ops.matmul.pack"] > 0):
        fail(f"pack route count {counts['ops.matmul.pack']} with "
             f"packed={packed}")


def check_mesh(mesh) -> None:
    """The mesh spans all four devices, and a sharded computation over
    it places one shard on each of them."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    devs = set(jax.devices())
    if set(mesh.devices.flat) != devs or len(devs) != 4:
        fail(f"mesh devices {mesh.devices} are not the host's 4 chips")
    probe = jax.jit(jax.shard_map(
        lambda x: x + jax.lax.axis_index("model"), mesh=mesh,
        in_specs=P("data", "model"), out_specs=P("data", "model")))
    out = probe(np.zeros((1, 4), np.int32))
    shards = {s.device: int(np.asarray(s.data).ravel()[0])
              for s in out.addressable_shards}
    if set(shards) != devs or sorted(shards.values()) != [0, 1, 2, 3]:
        fail(f"pack shards landed on {shards}")
    log(f"mesh: {dict(mesh.shape)} over devices "
        f"{sorted(d.id for d in devs)}; one shard per chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the pack-sharded path over 4 chips, "
                         "against the same requests on one chip")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run every phase on the CPU at the smoke preset "
                         "(interpret-mode kernels); still exits non-zero")
    args = ap.parse_args()

    if not (HERE / "src" / "repro").is_dir():
        fail(f"no repro package under {HERE / 'src'}: run from a checkout")
    sys.path.insert(0, str(HERE / "src"))
    import jax
    import numpy as np

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse_on_cpu:
        fail(f"jax finds no TPU (platform {dev.platform})")
    if args.four_chips and on_chip and device["count"] != 4:
        fail(f"--four-chips needs 4 chips, jax sees {device['count']}")

    from repro import configs as C, obs
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.tuning import dispatch

    clock = CompileClock()
    log(f"compile cache: {enable_compile_cache(HERE)}")
    tuning = HERE / ".cache" / "tuning" / "chip_smoke.json"
    tuning.unlink(missing_ok=True)    # analytic tiles, not a tuned cache
    dispatch.set_cache_path(tuning)
    obs.reset()

    cfg = C.get("smollm_360m")
    if args.rehearse_on_cpu:
        cfg = dataclasses.replace(C.get_smoke("smollm_360m"),
                                  compute_dtype="bfloat16",
                                  cache_dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    trace = make_trace(cfg.vocab_size, args.seed)
    log(f"model {cfg.name}: {cfg.n_layers} layers d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.d_head} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} {cfg.compute_dtype}; {len(trace)} "
        f"requests, prompts {min(len(t['prompt']) for t in trace)}-"
        f"{max(len(t['prompt']) for t in trace)} tokens")

    # On the chip "auto" already means the kernels; the CPU rehearsal
    # forces them (interpret mode) so every check below has work to do.
    def kernels():
        return (contextlib.nullcontext() if on_chip
                else ops.force_mode("kernel"))
    t0 = time.perf_counter()
    with kernels():
        rep, taps = serve(cfg, params, trace, args.seed)
    serve_s = time.perf_counter() - t0
    counts = route_counts()
    log(f"served {rep['tokens']} tokens for {len(rep['results'])} "
        f"requests in {serve_s:.3f}s incl. compile; "
        f"prefill_chunks={rep['prefill_chunks']} "
        f"decode_steps={rep['decode_steps']} "
        f"shared_steps={rep['shared_steps']} pages_hwm={rep['pages_hwm']}")
    check_routes(counts, packed=False)

    if args.four_chips:
        from jax.sharding import AxisType
        mesh = jax.make_mesh((1, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        check_mesh(mesh)
        obs.reset()
        with kernels():
            rep4, taps4 = serve(cfg, params, trace, args.seed,
                                pack_mesh=mesh)
        log(f"four chips: served {rep4['tokens']} tokens, "
            f"packed_gemms={rep4['packed_gemms']}")
        check_routes(route_counts(), packed=True)
        if rep4["packed_gemms"] <= 0:
            fail("no GEMM cleared the pack threshold")

    log(clock.line())
    ref16, ref32 = reference_logits(cfg, params, trace, rep["results"])
    worst = compare("one chip vs oracle", taps, trace, ref16, ref32)
    if args.four_chips:
        r16, r32 = reference_logits(cfg, params, trace, rep4["results"])
        worst = max(worst, compare("four chips vs oracle", taps4, trace,
                                   r16, r32))
        agree = sum(int(np.array_equal(rep["results"][t["id"]],
                                       rep4["results"][t["id"]]))
                    for t in trace)
        log(f"four chips vs one chip: {agree}/{len(trace)} requests with "
            f"identical greedy tokens")
        compare("four chips vs one chip", taps4, trace, ref16, ref32,
                other=(taps, rep["results"], rep4["results"]))
    log(f"max_logit_err={worst:.6g}")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}"
        f" bytes_limit={stats.get('bytes_limit', 'not reported')}")
    log(clock.line())

    if not on_chip:
        fail(f"rehearsal passed every phase, but jax finds no TPU "
             f"(platform {dev.platform})")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
