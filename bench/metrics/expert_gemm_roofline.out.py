"""Least time of the held experts' grouped matmuls the window's calls needed (the architecture's expert_gmm_cost: the held pairs, and the weights of the held experts each expert-layer call reached, the run's mean from the program's counters) over the device time of gama_expert_gmm plus that of the operations that stage its weight stacks, in percent; moves output_tok_s.

XLA may copy a layer's stack of held expert weights into VMEM ahead of
the kernel, in an operation of its own whose output has the stack's
shape; the weights' HBM read is then timed there and not in the kernel.
"""

from bench.lib import layers

KERNEL = "gama_expert_gmm"


def reached_per_layer_call():
    """Held experts that got a pair, per expert-layer call, over the run
    so far; None where the program counts none."""
    from repro.obs import get_obs
    counters = get_obs().registry.counters
    reached = counters.get("engine.moe.held_experts_reached")
    calls = counters.get("engine.moe.layer_calls")
    if reached is None or calls is None or not calls.value:
        return None
    return reached.value / calls.value


def staging_s(trace, shape) -> float:
    """Device seconds of the operations, other than the kernel, whose
    output is a held expert weight stack (E, d, F) or (E, F, d)."""
    e, d, f = shape.held_experts, shape.d_model, shape.expert_d_ff
    outputs = (f"bf16[{e},{d},{f}]", f"bf16[{e},{f},{d}]")
    total = 0.0
    for name, s in trace.op_s.items():
        text = name if " = " in name else trace.op_text.get(name, "")
        lhs, _, rhs = text.partition(" = ")
        if rhs.startswith(outputs) and KERNEL not in lhs:
            total += s
    return total / max(1, trace.devices)


def read(ctx):
    if not hasattr(ctx.arch, "expert_gmm_cost") \
            or not ctx.trace.kernel_s(KERNEL):
        return None
    reached = reached_per_layer_call()
    if reached is None:
        return None
    p = ctx.peaks
    least = 0.0
    for c in ctx.calls():
        flops, nbytes = ctx.arch.expert_gmm_cost(ctx.shape, c.rows, reached)
        least += max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    spent = ctx.trace.kernel_s(KERNEL) + staging_s(ctx.trace, ctx.shape)
    return 100.0 * least / spent if least else None
