"""Verifiers with one guarantee of the configuration broken, built on the
plain reference. `correct` has to come out false with any of them in the
program's place (run.py --control NAME; tests/test_correct.py). The
configuration states: every signature checked, every verdict exact. Each
takes the validator set (`pubkeys`, `powers`, `total_power` in the set's
order) before the rows; run.py binds it."""

from __future__ import annotations

import reference


def _unseen_after(cut: int, pubkeys, msgs, sigs):
    return reference.verify_rows(pubkeys[:cut], msgs[:cut], sigs[:cut]) + \
        [True] * (len(pubkeys) - cut)


def light(vals, pubkeys, msgs, sigs):
    """The control: VerifyCommitLight's rule where VerifyCommit is stated.
    In each commit of the rows (a commit's rows stand in the set's order, so
    a new one starts where the seat does not rise) it checks rows until more
    than 2/3 of the set's power has been seen and takes the rest unseen: the
    step that would tempt a later PR."""
    seat = {pk: i for i, pk in enumerate(vals.pubkeys)}
    seen, last, tallied = [], -1, 0
    for pk in pubkeys:
        if seat[pk] <= last:
            tallied = 0
        last = seat[pk]
        seen.append(tallied * 3 <= vals.total_power * 2)
        tallied += vals.powers[last]
    checked = iter(reference.verify_rows(*(
        [x for x, s in zip(xs, seen) if s] for xs in (pubkeys, msgs, sigs))))
    return [next(checked) if s else True for s in seen]


def half(vals, pubkeys, msgs, sigs):
    """Planted fault: half of the batch left out."""
    return _unseen_after(len(pubkeys) // 2, pubkeys, msgs, sigs)


def altered(vals, pubkeys, msgs, sigs):
    """Planted fault: one answer altered where it is produced."""
    mask = reference.verify_rows(pubkeys, msgs, sigs)
    at = len(mask) // 3
    mask[at] = not mask[at]
    return mask


def sound(vals, pubkeys, msgs, sigs):
    """Not a fault: the reference itself in the program's place, for the
    tests to see that the comparison passes what is right."""
    return reference.verify_rows(pubkeys, msgs, sigs)
