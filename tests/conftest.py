import os
import sys

# Tests must see ONE CPU device (smoke realism); the dry-run sets its own
# XLA_FLAGS in subprocesses. Ensure src is importable regardless of cwd.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The suite runs on the CPU (Pallas in interpret mode) even on a machine
# with an accelerator: a test run must never take the chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Every engine built under pytest runs with allocator-consistency and
# host<->device block-table mirror checks at stage boundaries (benchmarks
# leave this off; EngineConfig.debug_invariants=False opts a test out).
os.environ.setdefault("REPRO_DEBUG_INVARIANTS", "1")
