"""Record the small device trace that the trace-reduction tests replay.

    python3 chip_bench/record_trace_fixture.py OUT_DIR

Runs on a TPU only. Two small jitted programs stand in for a prefill
chunk and a decode step: each call is dispatched inside a host span named
like the harness's own (`cb.prefill`, `cb.decode`), two prefills are
dispatched back to back before a sync, as the serving engine does. The
profiler's trace is reduced to the normalized event list that
`chip_bench/trace.py` reads (`load_events`) and written to
`OUT_DIR/tpu_trace_small.json`, with a summary of the trace's planes and
lines on standard output.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    out_dir = sys.argv[1]
    sys.path.insert(0, os.path.dirname(HERE))
    import jax
    import jax.numpy as jnp

    from chip_bench import trace

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"record_trace_fixture: needs a TPU, found {dev.platform}")
    prefill = jax.jit(lambda w, x: jnp.tanh(x @ w) @ w.T)
    decode = jax.jit(lambda w, x: (x @ w).sum(axis=-1))
    w = jnp.ones((2048, 2048), jnp.bfloat16)
    xp = jnp.ones((512, 2048), jnp.bfloat16)
    xd = jnp.ones((8, 2048), jnp.bfloat16)
    prefill(w, xp).block_until_ready()
    decode(w, xd).block_until_ready()
    with tempfile.TemporaryDirectory(dir=out_dir) as tmp:
        with trace.profile(tmp):
            with trace.span("cb.window"):
                for _ in range(3):
                    with trace.span("cb.prefill"):
                        a = prefill(w, xp)
                    with trace.span("cb.prefill"):
                        b = prefill(w, xp)
                    with trace.span("cb.decode"):
                        c = decode(w, xd)
                    with trace.span("cb.sample"):
                        jax.block_until_ready((a, b, c))
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        print("xplane bytes", os.path.getsize(path))
        print(json.dumps(trace.describe(path), indent=1)[:20000])
        events = trace.load_events(path)
    with open(os.path.join(out_dir, "tpu_trace_small.json"), "w") as f:
        json.dump(events, f)
    print("events", {k: len(v) for k, v in events.items()
                     if isinstance(v, list)})


if __name__ == "__main__":
    main()
