"""Device: model FLOPs of the window's prefill and decode steps (2 N per
token, attention, the output head; chip_bench/costs) over their device
time times the bf16 peak (%)."""
from chip_bench import trace


def read(ctx):
    if ctx["peaks"] is None:
        return None
    ev, (lo, hi) = ctx["events"], ctx["window"]
    ms = (trace.launch_device_ms(ev, "cb.prefill", lo, hi)
          + trace.launch_device_ms(ev, "cb.decode", lo, hi))
    flops = ctx["host"].get("step_flops")
    if not ms or not flops:
        return None
    return 100.0 * flops / (sum(ms) / 1e3 * ctx["peaks"].bf16_flops)
