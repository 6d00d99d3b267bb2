"""Bring-up check on one TPU chip: the paper's serving path at LLaMA-65B's
published widths, its depth cut to 4 of 80 layers.

    python chip_smoke.py

Runs, in this one process, the serve and the checks that ``python -m
repro.launch.serve --arch llama_65b --published`` runs (the same
functions): every request completes with exactly its token budget, every
sampled logit row is finite, and two served requests replayed through a
float32, cache-free forward of the same weights agree with the engine —
its paged chunked-prefill logits within a bf16 tolerance, and every token
it emitted for them a near-best logit of the reference. The timings,
counters and stage breakdown it prints are a bring-up reading, not a
benchmark.

Exits non-zero, before doing any work, when JAX finds no TPU; and non-zero
when a check fails. On success the last line of standard output is one JSON
object naming the device.
"""
import json
import sys
from pathlib import Path

import jax


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    print(f"devices: {devices}")
    print(
        f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
        f"count: {len(devices)}"
    )
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this check runs only on the chip",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.serve import (
        check_published_serve,
        enable_compile_cache,
        print_published_checks,
        published_config,
        serve_published,
    )

    print(f"compile cache dir: {enable_compile_cache()}")
    cfg, reduced = published_config("llama_65b")
    run = serve_published(cfg, reduced, n_requests=32, seed=0, policy="hybrid")
    checks = check_published_serve(run)
    peak = dev.memory_stats()["peak_bytes_in_use"]

    print("--- bring-up reading (one run, not a benchmark) ---")
    print(f"weights init s: {run.init_s:.3f}")
    print(f"compile and warm-up s: {run.compile_warm_s:.3f}")
    print(f"serve wall s: {run.serve_s:.3f}")
    print_published_checks(run, checks)
    print(f"peak_bytes_in_use: {peak}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
