"""What the serving layer needs of a model: nothing under ``serving/``
imports a model class or asks a model's name.

A model is served through its CONFIG: a frozen, hashable dataclass (it
is the static argument of the engine's resident programs) with the
methods of :class:`ServedModel`.  ``models.llama.LlamaConfig``,
``models.afmoe.AfmoeConfig``, ``models.mla_moe.MlaMoeConfig``,
``models.looped.LoopedConfig`` and ``models.hybrid_ssm.HybridSsmConfig``
implement it.

The cache of one sequence is a tree of leaves per layer, and the pool
stacks whatever it is given (``SlotPool``: ``[capacity, *leaf]``).  The
serving layer reads a leaf's KIND off its name (:func:`leaf_kind`):

* ``cache_index``: how many positions the layer has written; the
  engine corrects it after a padded chunk, freezes it for inactive
  slots and resets it when a slot is freed.  It is the only state a
  new admission observes.
* ``window_*``: a ring of about a window of positions.  It does not
  grow with ``max_len``, and its rows are not the positions of a
  prefix, so a prefix cache refuses a model that declares one.
* ``stat_*``: small observations of the model's own calls; where a
  registry counts, they leave the device with a decode step's tokens.
  A float32 leaf travels as its bits (``kv_pool.pack_stats``).
  ``stat_experts [top_k]`` holds the experts a sequence's last token
  chose (``ServingMetrics.on_expert_choices`` counts them against the
  model's ``held = (first, count)``); ``stat_expert_rows [2]`` adds up,
  over every call that wrote the slot, the rows the expert loop
  computed and the held assignments they were computed for
  (``ServingMetrics.on_expert_rows`` counts what it grew by);
  ``stat_exit_pdf [loop_steps]`` the distribution a looped model's exit
  gate puts over the passes for the sequence's last token
  (``ServingMetrics.on_exit_pdf`` keeps its mean expected pass).
* ``state_*``: what a recurrent layer remembers of the sequence, of a
  fixed size whatever ``max_len`` is, read whole by every call.  It has
  no "above the index" to hide anything in, so the model keeps this
  rule, and the engine's shortcuts lean on it: **a state leaf is what
  the model left after the LIVE tokens behind ``cache_index``, and a
  call that starts at index 0 starts from zero state whatever the leaf
  holds.**  A token whose ``live`` is False (a chunk's padded tail, a
  slot that sits a decode step out, a slot nobody holds) leaves the
  leaf as it was: the model freezes it, the engine masks nothing.  A
  freed slot's index is reset and nothing else, so the next admission
  starts from nothing; a step a slot ran past its request's end (the
  program one ahead) lands in a state nobody reads again.  Restoring
  rows does not restore a state and an index rolled back does not roll
  one back: a prefix cache and the speculative step refuse a model
  that declares one.  A layer MAY hold ``state_*`` leaves BESIDE full
  leaves under its one ``cache_index`` (``models/hybrid_ssm.py``: a
  state-space mixer and attention on one normed input): each leaf keeps
  its own kind's rule, the pool's gauges count both, and the refusals
  above stand.
* anything else: ``max_len`` positions along one axis ("full"),
  whatever a position holds: a key or a value of every head, or one
  latent that all heads share.

A model whose leaves hold something compressed MAY also declare
``rebuilt_positions(start, tokens) -> int``: the cached positions whose
keys and values a call of ``tokens`` tokens at cache index ``start``
rebuilds from its leaves before it can attend, summed over layers (0
where the call reads the leaves as they stand); the engine counts it a
prefill chunk (``bf_serving_latent_expanded_positions_total``).

A model whose call of several tokens reads less than every reserved row
MAY declare ``chunk_streamed_positions(start, tokens) -> ((kind, rows),
...)``: the cache rows such a call reads to attend, summed over the
kind's layers, from the same two lengths; the engine counts them a
prefill chunk (``bf_serving_chunk_streamed_positions_total{kind}``).  A
model that declares neither counts neither.

A model whose residual path is several streams, mixed a token at a time
around its sublayers, MAY declare ``residual_streams`` (how many) and
``mixed_sublayers`` (around how many sublayers a token is mixed; 0 for a
plain residual); the engine sets ``bf_hc_streams`` and counts the live
tokens of every chunk and decode step times the sublayers
(``bf_hc_mixed_tokens_total``).  The streams themselves live and die
inside one ``apply_cached``: no leaf holds one.

A model with recurrent layers MAY declare ``state_layers`` (how many):
the engine counts the live tokens of every chunk and decode step times
the layers (``bf_serving_state_chunk_tokens_total``,
``bf_serving_state_steps_total``).  It MAY also declare
``state_streamed_steps(decoding, capacity) -> int``: the slots whose
state a single-token step READS times those layers, from the count of
decoding slots and the pool's capacity (the decoding slots under a
kernel over the live rows, every slot where the step is mapped over the
pool); the engine counts it a decode step
(``bf_serving_state_streamed_steps_total``).

A model whose layers run several times over the same weights MAY
declare ``loop_steps`` (the passes a token makes through its layers;
absent means 1) beside ``n_layers`` (the layers of one pass).  Every
pass of every layer keeps keys and values of its own, so such a model's
``cache_kinds()`` and ``streamed_positions()`` count ``loop_steps x
n_layers`` layers, and every counter and gauge built on them
(``bf_serving_streamed_positions_total``, ``bf_serving_cache_bytes``)
with them; the engine sets the gauge ``bf_serving_loop_steps`` and
counts the live tokens of every chunk and decode step times the passes
times the layers (``bf_serving_loop_layer_tokens_total``).  Its leaves
may be STACKS over (pass, layer), rolled loops carry them, and one
``cache_index`` serves them all: the engine, the pool and the prefix
cache find a leaf's kind by its name and its position axis by what
scales with ``max_len``, and ask nothing else of a leaf's shape.

A model whose cache of ONE sequence is large MAY declare
``apply_in_pool(params, pool, slot, tokens, live=None) -> (logits,
pool')``: ``apply_cached`` on slot ``slot`` of the pool's stacked leaves
(``[capacity, *leaf]``), its rows written where they lie.  The engine's
prefill-chunk program then calls it in place of cutting the slot's tree
out of the pool and putting it back, which holds a second copy of the
slot while the call runs (1.125 GiB at 192 layer applications x 768
positions), and corrects the slot's index as it does after
``apply_cached``.
"""

from __future__ import annotations

from typing import Protocol, Tuple

INDEX, WINDOW, FULL, STAT, STATE = "index", "window", "full", "stat", "state"


class ServedModel(Protocol):
    """A model's config, as the engine, the pool and the prefix cache
    use it."""

    vocab_size: int

    def serving_layout(self, max_len: int, *, chunk: int = 1,
                       kv_quant: str = "none", weight_quant: str = "none",
                       decode_attn: str = "xla") -> "ServedModel":
        """The config the resident programs run under: caches of
        ``max_len`` positions, calls of at most ``chunk`` tokens."""

    def init_cache(self, batch_size: int, max_len: int):
        """The zeroed cache tree of ``batch_size`` sequences."""

    def apply_cached(self, params, cache, tokens, all_logits=False,
                     live=None):
        """Append ``tokens [B, T]`` at the cache index: ``(logits [B, 1
        or T, vocab], cache')``; the last position's logits alone unless
        ``all_logits``.  ``live [B, T]`` is False where a token is
        padding (a slot that does not decode, a chunk's tail): a model
        may skip what only such a token would need; what it returns for
        one is never read."""

    def cache_kinds(self) -> dict:
        """``{"full" | "window": (layers, most positions a query
        attends, or None for all behind it)}``."""

    def streamed_positions(self, positions) -> Tuple[Tuple[str, int], ...]:
        """``((kind, positions), ...)``: cache positions one
        single-token step fetches from the pool, summed over the kind's
        layers, when slot ``i``'s query sits at ``positions[i]`` (one
        entry a slot of the pool; ``-1`` for a slot that does not
        decode, which computes too).  A lowering that reads every
        reserved row behind a mask streams them all, whatever is
        live."""


def leaf_kind(path) -> str:
    """The kind of the cache leaf at ``path`` (a key path of
    ``jax.tree_util``), by the leaf's name."""
    name = getattr(path[-1], "key", None) or ""
    if name == "cache_index":
        return INDEX
    if name.startswith("window_"):
        return WINDOW
    if name.startswith("stat_"):
        return STAT
    if name.startswith("state_"):
        return STATE
    return FULL


def attended_positions(kinds: dict, lengths) -> Tuple[Tuple[str, int], ...]:
    """``((kind, positions), ...)``: cache positions a decode step over
    sequences of ``lengths`` attends, summed over the kind's layers."""
    out = []
    for kind, (layers, most) in kinds.items():
        seen = sum(n if most is None else min(n, most) for n in lengths)
        out.append((kind, layers * seen))
    return tuple(out)
