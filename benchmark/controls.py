"""Verifiers with one guarantee of the configuration broken, built on the
plain reference. `correct` has to come out false with any of them in the
program's place (run.py --control NAME; tests/test_correct.py). The
configuration states: every signature checked, every verdict exact."""

from __future__ import annotations

import reference


def _unseen_after(cut: int, pubkeys, msgs, sigs):
    return reference.verify_rows(pubkeys[:cut], msgs[:cut], sigs[:cut]) + \
        [True] * (len(pubkeys) - cut)


def light(pubkeys, msgs, sigs):
    """The control: VerifyCommitLight's rule where VerifyCommit is stated.
    It checks rows until more than 2/3 of the (equal) power has signed and
    takes the rest unseen: the step that would tempt a later PR."""
    return _unseen_after(len(pubkeys) * 2 // 3 + 1, pubkeys, msgs, sigs)


def half(pubkeys, msgs, sigs):
    """Planted fault: half of the batch left out."""
    return _unseen_after(len(pubkeys) // 2, pubkeys, msgs, sigs)


def altered(pubkeys, msgs, sigs):
    """Planted fault: one answer altered where it is produced."""
    mask = reference.verify_rows(pubkeys, msgs, sigs)
    at = len(mask) // 3
    mask[at] = not mask[at]
    return mask


def sound(pubkeys, msgs, sigs):
    """Not a fault: the reference itself in the program's place, for the
    tests to see that the comparison passes what is right."""
    return reference.verify_rows(pubkeys, msgs, sigs)
