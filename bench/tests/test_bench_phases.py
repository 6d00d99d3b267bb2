"""The readers of the engine's step phases and pool writes, on a toy serve on
the CPU; ``fused_decode_ms`` on a hand-built reduction; and every one of
them reading None, without raising, on records of a program that lacks
them."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import drive
from harness.cell import load_cell, reader
from harness.reduce import Reduction
from repro.core.types import StageKind, StageRecord

ROOT = Path(__file__).resolve().parents[2]
TOY_MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=160, vocab_size=256, num_hidden_layers=2,
)
HOST = ("host_schedule_pct", "host_kv_pct", "host_prepare_pct", "host_commit_pct")
COUNTERS = HOST + ("host_launch_pct", "pool_writes_per_step")


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("cache")))
    try:
        cell = load_cell("granite-3-8b.gsm8k-backlog", ROOT)
        cell.config.update(TOY_MODEL)
        setup = drive.build(cell.config, cell.traffic, 11, 1e12, 4)
        yield drive.serve(setup, 11, 3.0)
    finally:
        mp.undo()


@pytest.mark.parametrize("name", COUNTERS)
def test_counter_readers_on_a_toy_serve(toy_run, name):
    v = reader(name)(toy_run)
    assert v is not None and v >= 0.0
    if name == "pool_writes_per_step":
        # every step's decode lanes grow a page now and then
        assert v > 0.0


def test_host_phases_add_up_to_the_harness_gap(toy_run):
    phases = sum(reader(n)(toy_run) for n in HOST)
    gap = reader("host_gap_pct.backlog")(toy_run)
    assert phases == pytest.approx(gap, abs=0.5)
    assert phases <= gap + 1e-9       # the phases lie inside serve_step


def _decode_step(t1, rounds):
    return SimpleNamespace(
        t0=t1 - 0.01, t1=t1,
        stage=StageRecord(kind=StageKind.DECODE, t_start=0.0, t_end=0.01,
                          bin_index=0, rounds=rounds),
    )


def _traced_run(programs, runs=((0.0, 1.0),)):
    """A profile 1 s long from host time 10.0; the harness's segment ends at
    10.8, and the step in flight then ends at 10.95, inside the profile."""
    red = Reduction(window=(0.0, 1e6), devices=1, busy_s=0.9, window_s=1.0,
                    programs=programs, ops={}, runs=list(runs))
    steps = [_decode_step(9.9, 8), _decode_step(10.1, 1), _decode_step(10.2, 2),
             _decode_step(10.95, 1), _decode_step(11.5, 4)]
    return SimpleNamespace(reduction=red, steps=steps, traced=(10.0, 10.8))


def test_fused_decode_ms_counts_only_the_fused_program():
    run = _traced_run({"jit_fused_decode(1)": (4, 0.08), "jit(scatter)": (40, 0.02)})
    # 4 rounds in the profile: the steps before it and after it are out
    assert reader("fused_decode_ms")(run) == pytest.approx(20.0)


def test_fused_decode_ms_is_none_without_names_or_modules():
    read = reader("fused_decode_ms")
    assert read(_traced_run({"jit__unknown(7)": (3, 0.06)})) is None
    assert read(_traced_run({"jit_fused_decode(1)": (0, 0.06)}, runs=())) is None
    assert read(SimpleNamespace(reduction=None)) is None


def test_counter_readers_are_none_on_records_without_phases():
    """A program whose stage records predate the phases and the counter."""
    step = SimpleNamespace(t0=0.5, t1=1.0, stage=SimpleNamespace(duration=0.3))
    run = SimpleNamespace(window_steps=lambda: [step], window_s=1.0)
    for name in COUNTERS:
        assert reader(name)(run) is None
