"""Faults planted under the timed path, to see ``correct`` come out false.

Each takes a ``pytest.MonkeyPatch`` and breaks the program where a later
change could: the decode step returns its state unchanged, a served token is
altered where the decode block produces it, or the prefill's state is never
spliced into its slot.  ``bench/tests/test_harness.py`` plants them at smoke
size; ``bench/control.py --fault`` reads them at a cell's own size.
"""

from __future__ import annotations


def decode_state_unchanged(mp) -> None:
    from repro.models import lm

    orig = lm.decode_step

    def broken(params, cfg, tokens, caches, pos, **kw):
        logits, _ = orig(params, cfg, tokens, caches, pos, **kw)
        return logits, caches

    mp.setattr(lm, "decode_step", broken)


def token_altered(mp) -> None:
    from repro.runtime import server

    orig = server.DecodeServer._make_block_fn

    def broken(self, k):
        fn = orig(self, k)

        def call(*args):
            carry, (toks, emitted, done, finite) = fn(*args)
            toks = toks.at[k // 2].set((toks[k // 2] + 1) % self.cfg.vocab)
            return carry, (toks, emitted, done, finite)

        return call

    mp.setattr(server.DecodeServer, "_make_block_fn", broken)


def prefill_not_spliced(mp) -> None:
    from repro.runtime import server

    mp.setattr(server, "splice_cache", lambda caches, *a, **k: caches)


FAULTS = {"decode_state_unchanged": decode_state_unchanged,
          "token_altered": token_altered,
          "prefill_not_spliced": prefill_not_spliced}
