"""Whether what the timed path served is correct.

After the window (and after ``memory_peak_bytes`` is read and the KV pool is
freed), a sample of the requests the serve finished, drawn from the seed and
always holding the longest, is replayed through the float32 reference
(``reference.py``) with its own served tokens (teacher forcing). At each
position that produced a served token, the token's logit is compared with
the reference's best logit there:

    gap = (max_v ref[v] - ref[served]) / std_v(ref)

The number compared is the widest gap over the sample. Under greedy
decoding a correct bf16 program picks the reference's argmax or a near tie;
a token from a wrong KV page, another slot's context or a corrupted sampler
scores like a random logit, several standard deviations below the best.

Besides, every finished request must carry exactly its output budget, and
every served token must lie in the vocabulary (the greedy sampler turns a
non-finite logit row into id V).

The control (``served_gaps(..., control=True)``) runs the reference in fp8
in the program's place: at each of the same positions it takes the token the
fp8 forward puts first and reads that token's gap in the float32 reference.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .traffic import prompt_tokens


def sample(run, seed: int, served_tokens: int, max_requests: int) -> List[int]:
    """Request ids to check: the longest finished in the window (most
    served tokens, then lowest id), then others drawn from the seed until
    ``served_tokens`` are covered or ``max_requests`` are taken."""
    done = sorted(
        (rid for rid, r in run.reqs.items()
         if r.done is not None and run.ws < r.done <= run.end),
        key=lambda rid: (-run.reqs[rid].output, rid),
    )
    if not done:
        return []
    picked = [done[0]]
    rng = np.random.default_rng(np.random.SeedSequence([abs(int(seed)), 1]))
    for i in rng.permutation(len(done) - 1) + 1:
        if len(picked) >= max_requests or sum(
            run.reqs[r].output for r in picked
        ) >= served_tokens:
            break
        picked.append(done[int(i)])
    return picked


@jax.jit
def _gaps(ref, nxt, mask):
    """Row i's gap for token ``nxt[i]`` in reference logits ``ref``."""
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    gap = (best - got) / ref.std(axis=-1)
    agree = (ref.argmax(axis=-1) == nxt)
    return jnp.where(mask, gap, 0.0), jnp.where(mask, agree, False)


def _rows(prompt: np.ndarray, served: np.ndarray, pad: int):
    """Input tokens, the token each row produced, and the rows that did."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    nxt = np.zeros(pad, np.int32)
    full = np.concatenate([prompt, served])
    nxt[: len(full) - 1] = full[1:]
    mask = np.zeros(pad, bool)
    mask[len(prompt) - 1: len(prompt) - 1 + len(served)] = True
    return seq, nxt, mask


def _pad(conf: dict, mix: dict) -> int:
    q = reference.Q_BLOCK
    return -(-mix["max_len"] // q) * q


def served_gaps(conf: dict, mix: dict, params, rid: int, prompt_len: int,
                served: np.ndarray, control: bool = False) -> Dict[str, np.ndarray]:
    """Gaps of one request's served tokens; with ``control`` also the gaps
    of the tokens the fp8 control would have put first."""
    pad = _pad(conf, mix)
    prompt = prompt_tokens(rid, prompt_len, conf["vocab_size"])
    seq, nxt, mask = _rows(prompt, served, pad)
    ref = reference.logits(conf, params, seq, pad)
    g, agree = _gaps(ref, jnp.asarray(nxt), jnp.asarray(mask))
    out = {"gap": np.asarray(g)[mask], "agree": np.asarray(agree)[mask]}
    if control:
        low = reference.logits(conf, params, seq, pad, quant="fp8")
        pick = jnp.argmax(low, axis=-1).astype(jnp.int32)
        del low
        gc, _ = _gaps(ref, pick, jnp.asarray(mask))
        out["control_gap"] = np.asarray(gc)[mask]
    del ref
    return out


def budgets(run, vocab: int, generated: Dict[int, List[int]]) -> Dict[str, int]:
    """Finished requests whose token count is off, and served tokens outside
    the vocabulary, over every request the run served."""
    wrong_count = sum(
        1 for rid, r in run.reqs.items()
        if r.done is not None and len(generated.get(rid, ())) != r.output
    )
    out_of_vocab = sum(
        1 for rid in run.reqs for t in generated.get(rid, ()) if not 0 <= t < vocab
    )
    return {"budget_mismatches": wrong_count, "out_of_vocab": out_of_vocab}


def check(run, seed: int, params, generated: Dict[int, List[int]],
          control: bool = False) -> Dict[str, object]:
    """Every number compared, with the sample's details."""
    setup = run.setup
    conf, mix = setup.conf, setup.mix
    plan = mix["check"]
    rids = sample(run, seed, plan["served_tokens"], plan["max_requests"])
    gaps, agree, ctrl = [], [], []
    for rid in rids:
        served = np.asarray(generated[rid], np.int32)
        if len(served) != run.reqs[rid].output or not (
            (served >= 0) & (served < conf["vocab_size"])
        ).all():
            continue                       # counted by budgets(); no replay
        g = served_gaps(conf, mix, params, rid, run.reqs[rid].prompt, served,
                        control=control)
        gaps.append(g["gap"])
        agree.append(g["agree"])
        if control:
            ctrl.append(g["control_gap"])
    gap = np.concatenate(gaps) if gaps else np.zeros(0)
    out: Dict[str, object] = {
        "checked_requests": len(gaps),
        "checked_tokens": int(gap.size),
        "argmax_agreement": float(np.concatenate(agree).mean()) if agree else None,
        "token_gap_max": float(gap.max()) if gap.size else None,
    }
    if control:
        cg = np.concatenate(ctrl) if ctrl else np.zeros(0)
        out["control_gap_max"] = float(cg.max()) if cg.size else None
    out.update(budgets(run, conf["vocab_size"], generated))
    return out


def verdict(numbers: Dict[str, object], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]) for the numbers that have limits.
    A run that checked no token is not correct."""
    rows = [
        ("token_gap_max", numbers["token_gap_max"], limits["token_gap_max"]),
        ("budget_mismatches", numbers["budget_mismatches"], 0),
        ("out_of_vocab", numbers["out_of_vocab"], 0),
    ]
    ok = numbers["checked_tokens"] > 0 and all(
        v is not None and v <= lim for _, v, lim in rows
    )
    return ok, rows
