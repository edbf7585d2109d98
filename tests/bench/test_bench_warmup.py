"""The warm-up's reckoning of reachable packed-prefill shapes covers every
admission batch the planner can form, checked exhaustively at small
sizes."""
import itertools

import pytest

import benchpath  # noqa: F401
from harness.serve import admission_batches, packed_bucket, pow2_at_least


def key(lens, slot_len):
    return (packed_bucket(sum(lens)), min(slot_len, pow2_at_least(max(lens))),
            pow2_at_least(len(lens)))


def reachable(chunk, p_min, p_max, n_slots, slot_len, recompute):
    """Every batch a tick can admit: whole prompts while the budget lasts,
    the last cut to what is left; and continuations of one segment."""
    keys, sizes = set(), set()
    whole = range(p_min, min(p_max, chunk) + 1)
    for n in range(1, n_slots + 1):
        for ws in itertools.combinations_with_replacement(whole, n - 1):
            left = chunk - sum(ws)
            if left <= 0:
                continue
            for last in range(1, min(left, p_max) + 1):
                lens = list(ws) + [last]
                keys.add(key(lens, slot_len))
                sizes.add(n)
    cont = range(1, (p_max if recompute else min(p_max, chunk)) + 1)
    keys |= {key([c], slot_len) for c in cont}
    return keys, sizes


@pytest.mark.parametrize("chunk,p_min,p_max,n_slots,recompute", [
    (16, 3, 20, 6, False), (16, 3, 20, 6, True), (24, 2, 12, 5, False),
    (12, 1, 30, 4, True)])
def test_every_reachable_shape_is_warmed(chunk, p_min, p_max, n_slots,
                                         recompute):
    slot_len = 64
    warmed = admission_batches(chunk, p_min, p_max, n_slots, slot_len,
                               recompute)
    want, sizes = reachable(chunk, p_min, p_max, n_slots, slot_len,
                            recompute)
    got = {key(lens, slot_len) for lens in warmed}
    assert want <= got
    assert sizes <= {len(lens) for lens in warmed}
    # and nothing warmed is out of reach
    assert got <= want
