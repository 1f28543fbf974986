"""Compile a cell's programs at their real sizes for a described TPU v5e
chip, without one attached.

    JAX_PLATFORMS=cpu python3 -m bench.rehearse --workload phi3m.docs

Lowers and compiles, with the Pallas kernels as the chip runs them (not
interpret mode), the two programs that carry a cell: the batched paged
decode step over all of the mix's slots, and the forward of one full
prefill chunk against a slot's dense scratch.  It
prints each program's ``memory_analysis()``; the compiler refuses what
the chip would refuse (tiling, scoped VMEM, programs that do not fit).
Nothing runs, so it says nothing about results or times.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="phi3m.docs")
    ap.add_argument("--slots", type=int, default=0,
                    help="compile for this many slots instead of the mix's")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.lib import model, program
    program.import_program()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels import ops
    from repro.models import decode_step, forward, init_cache
    from repro.models import init_paged_cache
    from repro.tuning import dispatch

    from bench.cell import load_cell
    spec = load_cell(ROOT, args.workload)
    eng = spec["mix"]["engine"]
    shape = model.load_shape(ROOT, spec["config_file"],
                             spec["cell"]["config"])
    arch = model.arch_of(shape)
    cfg = arch.program_config(shape)
    dispatch.set_cache_path(ROOT / ".cache" / "bench" / "rehearse.json")

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    weights = {k: jax.ShapeDtypeStruct(v, jnp.dtype(shape.dtype))
               for k, v in arch.weight_shapes(shape)[0].items()}
    params = on_chip(arch.program_params(weights))
    slots, ps = args.slots or eng["slots"], eng["page_size"]
    max_pages = -(-eng["max_len"] // ps)
    pool = on_chip(jax.eval_shape(
        lambda: init_paged_cache(cfg, slots * max_pages, ps)))
    scratch = on_chip(jax.eval_shape(
        lambda: init_cache(cfg, 1, max_pages * ps)))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)

    programs = {
        "decode_step": (
            jax.jit(lambda p, t, pos, bt, c: decode_step(
                p, t, pos, cfg, c, block_tables=bt)),
            (params, i32(slots), i32(slots), i32(slots, max_pages), pool)),
        f"prefill_chunk_{eng['prefill_chunk']}": (
            jax.jit(lambda p, b, c, pos: forward(
                p, b, cfg, caches=c, cache_pos=pos)[:2]),
            (params, {"tokens": i32(1, eng["prefill_chunk"])}, scratch,
             i32())),
    }
    ops._interpret = lambda: False     # the chip's kernels, not the emulator
    ok = True
    with ops.force_mode("kernel"):
        for name, (fn, args) in programs.items():
            t0 = time.perf_counter()
            try:
                compiled = fn.lower(*args).compile()
            except Exception as e:            # what the chip would refuse
                ok = False
                print(f"{name}: REFUSED: {type(e).__name__}: {e}"[:2000])
                continue
            mem = compiled.memory_analysis()
            print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; "
                  f"arguments {mem.argument_size_in_bytes} B, outputs "
                  f"{mem.output_size_in_bytes} B, temporaries "
                  f"{mem.temp_size_in_bytes} B, aliased "
                  f"{mem.alias_size_in_bytes} B; kernels: "
                  f"{compiled.as_text().count('tpu_custom_call')}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
