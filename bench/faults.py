"""Faults planted under the timed path, for readings of the check's upper
limits and for the tests that see ``correct`` come out false.

Each fault takes a ``setattr`` (``monkeypatch.setattr`` in a test, the
builtin in a process that runs nothing else) and returns the name of
the number compared that has to catch it.  The benchmark's own runs
plant none of them.
"""
from __future__ import annotations

from typing import Callable, Dict


def token_altered(patch) -> str:
    """Where a token is produced: the verify step commits the token after
    the cloud's argmax."""
    from repro.serve.spec import _SpecDraftMixin

    orig = _SpecDraftMixin._verify_impl

    def altered(self, k, *args):
        t, n_commit, cur, cache, pos = orig(self, k, *args)
        t = (t + 1) % self.cfg.vocab
        cur = (cur + 1) % self.cfg.vocab
        return t, n_commit, cur, cache, pos

    patch(_SpecDraftMixin, "_verify_impl", altered)
    return "max_logit_gap"


def state_unchanged(patch) -> str:
    """The verify step returns the KV cache it was given, unwritten."""
    from repro.serve.spec import _SpecDraftMixin

    orig = _SpecDraftMixin._verify_impl

    def stale(self, k, blocks, tail, blobs, scales, zps, drafts, cache, pos,
              bt):
        t, n_commit, cur, _, new_pos = orig(self, k, blocks, tail, blobs,
                                            scales, zps, drafts, cache, pos,
                                            bt)
        return t, n_commit, cur, cache, new_pos

    patch(_SpecDraftMixin, "_verify_impl", stale)
    return "max_logit_gap"


def half_the_batch_left_out(patch) -> str:
    """Every second request of a call comes back with no tokens."""
    from repro.serve.engine import CollaborativeServingEngine

    orig = CollaborativeServingEngine.generate_requests

    def dropped(self, reqs):
        out = orig(self, reqs)
        for r in reqs[1::2]:
            r.out_tokens.clear()
        return out

    patch(CollaborativeServingEngine, "generate_requests", dropped)
    return "failed_requests"


def draft_altered(patch) -> str:
    """Where a draft token is produced: the edge's draft suffix proposes
    the token after its argmax.  Greedy verify still serves the cloud's
    tokens, so only the drafts' acceptance shows it."""
    from repro.serve.spec import _SpecDraftMixin

    orig = _SpecDraftMixin._spec_draft_impl

    def altered(self, k, *args):
        blobs, scales, zps, drafts, e_cache, d_cache = orig(self, k, *args)
        return (blobs, scales, zps, (drafts + 1) % self.cfg.vocab, e_cache,
                d_cache)

    patch(_SpecDraftMixin, "_spec_draft_impl", altered)
    return "draft_miss_share"


FAULTS: Dict[str, Callable] = {f.__name__: f for f in (
    token_altered, state_unchanged, half_the_batch_left_out, draft_altered)}
