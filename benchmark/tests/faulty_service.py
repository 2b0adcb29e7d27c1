"""Services with the timed path broken underneath, for the tests that must
see ``correct`` come out false. Deployed like the real one, through the
configuration's ``service`` key (``benchmark/tests/data/configs``)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_service import ServeBench  # noqa: E402


class AlteredToken(ServeBench):
    """A token altered where it is produced: the engine's sampler returns
    the id after the one it chose, in prefill and in every decode step."""

    def __init__(self, spec):
        from kubetorch_tpu.serve import engine as E
        chosen = E._sample_slots

        def altered(logits, *a, **kw):
            tok, lp = chosen(logits, *a, **kw)
            return (tok + 1) % logits.shape[-1], lp

        E._sample_slots = altered
        super().__init__(spec)


class MiscountedTokens(ServeBench):
    """The program's own token counter, which ``serve_tok_s`` is read from,
    counts a tenth too many."""

    def counters(self):
        c = super().counters()
        c["tokens_generated"] = int(1.1 * c["tokens_generated"])
        return c
