"""The traffic mix: the same seed draws the same requests, another seed
the same work in another order, and the draws keep the mix's moments."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["gsm8k-backlog"])
def test_same_seed_same_requests(name):
    a = traffic.draw(_mix(name), 2**33 + 5)
    b = traffic.draw(_mix(name), 2**33 + 5)
    assert a == b


@pytest.mark.parametrize("name", ["gsm8k-backlog"])
def test_other_seed_same_work_other_order(name):
    mix = _mix(name)
    a = traffic.draw(mix, 1)
    b = traffic.draw(mix, 2)
    key = lambda d: (d.prompt, d.output, d.estimate)      # noqa: E731
    assert sorted(map(key, a)) == sorted(map(key, b))
    assert [key(d) for d in a] != [key(d) for d in b]
    assert {d.rid for d in a}.isdisjoint({d.rid for d in b})
    assert max(d.rid for d in a + b) < 2**31


def test_gsm8k_moments():
    d = traffic.draw(_mix("gsm8k-backlog"), 3)
    p = np.array([x.prompt for x in d])
    o = np.array([x.output for x in d])
    assert len(d) == 1600
    assert p.min() >= 1 and abs(p.mean() - 68.43) < 2.0 and abs(p.std() - 25.04) < 2.0
    assert o.min() >= 1 and o.max() <= 512
    # the paper's post-cap moments (Table III): 344.83 ± 187.99
    assert abs(o.mean() - 344.83) < 12 and abs(o.std() - 187.99) < 10


def test_prompt_tokens_follow_the_request_id():
    a = traffic.prompt_tokens(123, 40, 49152)
    assert (a == traffic.prompt_tokens(123, 40, 49152)).all()
    assert a.min() >= 1 and a.max() < 49152 and a.dtype == np.int32
    assert not (a == traffic.prompt_tokens(124, 40, 49152)).all()
