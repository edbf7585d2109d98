"""Whether what the window served is right: a sample of the finished
requests, drawn from the seed and holding the longest, replayed through the
plain float32 reference (``bench/models/<reference>.py``).

For each sampled request the reference reads the prompt, the token the
prefill put first and every served token, once, and gives at each of those
positions the gap by which the served token's logit lies below the
reference's best. The widest gap over the sample is compared with the
configuration's limit; a served stream of the wrong length fails outright.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness.traffic import rng_for


def sample(done: Sequence[int], arrivals: Dict, k: int, seed: int
           ) -> List[int]:
    """``k`` finished rids: the longest (prompt + output), then a seeded
    draw of the rest."""
    if not done:
        return []
    longest = max(done, key=lambda r: (arrivals[r].prompt_len
                                       + arrivals[r].output_len, r))
    rest = sorted(r for r in done if r != longest)
    rng = rng_for(seed, 5)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(rids, arrivals, prompts, seeds, served, length: int):
    """(rid, padded tokens, first gap position, gap count) per request
    whose first token was captured; prompts are indexed by arrival."""
    out = []
    for rid in rids:
        if rid not in seeds:
            continue
        p = np.asarray(prompts[rid], np.int32)
        toks = np.concatenate([p, [seeds[rid]], served[rid]]).astype(np.int32)
        if len(toks) > length:
            raise ValueError(f"request {rid}: {len(toks)} tokens exceed the "
                             f"reference length {length}")
        pad = np.zeros(length, np.int32)
        pad[:len(toks)] = toks
        out.append((rid, pad, len(p) - 1, len(served[rid]) + 1))
    return out


def gap_fn(family, m: Dict, mode: str):
    return jax.jit(lambda w, t: family.token_gaps(w, m, t, mode))


def widest_gap(fn, weights, seqs) -> float:
    worst = 0.0
    for _, toks, start, n in seqs:
        g = np.asarray(fn(weights, jnp.asarray(toks)))[start:start + n]
        worst = max(worst, float(g.max()))
    return worst


def compare(family, m: Dict, weights, conf: Dict, arrivals: Dict, prompts,
            done: Sequence[int], seeds: Dict[int, int],
            served: Dict[int, List[int]], seed: int, control: bool = False):
    """The numbers compared, each as [value, limit] (all upper limits),
    the count of served tokens compared, and (``control``) the widest gap
    of the int8 and fp8 controls on the same sequences."""
    lim = conf["check"]
    rids = sample(done, arrivals, int(lim["requests"]), seed)
    wrong = sum(len(served[r]) != arrivals[r].output_len for r in rids)
    seqs = sequences(rids, arrivals, prompts, seeds, served,
                     int(conf["serving"]["cache_len"]))
    gap = widest_gap(gap_fn(family, m, "ref"), weights, seqs) if seqs \
        else float("inf")
    ctl = ({mode: widest_gap(gap_fn(family, m, mode), weights, seqs)
            for mode in ("int8", "fp8")} if control and seqs else None)
    return {
        "logit_gap": [gap, float(lim["max_logit_gap"])],
        "wrong_lengths": [wrong, 0],
        "unchecked": [len(rids) - len(seqs), 0],
    }, sum(n for *_, n in seqs), ctl


def passed(checks: Dict[str, list]) -> bool:
    """Every number at or under its limit."""
    return all(v <= lim for v, lim in checks.values())


def control_verdicts(checks: Dict[str, list], ctl: Dict[str, float]
                     ) -> Dict[str, Dict]:
    """Each control judged as the program is: its widest gap put in the
    program's place among the numbers compared, under the same limits.
    A sound check reads every control not correct."""
    out = {}
    for mode, gap in ctl.items():
        theirs = {**checks, "logit_gap": [gap, checks["logit_gap"][1]]}
        out[mode] = {"logit_gap": theirs["logit_gap"],
                     "correct": passed(theirs)}
    return out
