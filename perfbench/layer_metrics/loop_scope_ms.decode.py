"""Device time a decode step of the operations under ``bf.loop`` (the
passes of ``bluefog_tpu.models.looped``: every layer application of
every pass, the final norm and the exit gate after each; embedding,
head and sampling lie outside), over the executions of the decode
program in the traced stretch (``harness/loop_scopes.py``); prints the
attention under ``bf.loop.attn`` and the rest of the loop apart, and
what lies outside.  Nothing where the program writes no such scope."""

from perfbench.harness import loop_scopes


def reduce(trace, spans, ctx):
    return loop_scopes.loop_ms(__file__, trace, "decode")
