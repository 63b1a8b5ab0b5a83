"""Serving metrics + request-lifecycle spans, on the observe substrate.

Numbers a serving operator actually pages on:

* **TTFT** (time to first token): submit -> first generated token, the
  user-visible latency of the prefill path + queueing.
* **Request latency**: submit -> retire.
* **Aggregate tokens/s**: generated tokens over the serving window — the
  throughput continuous batching exists to maximize.
* **Slot occupancy / queue depth**: sampled once per engine step; low
  occupancy under load means admission is the bottleneck, deep queues
  mean capacity is.

Everything is published twice, through the unified observability layer
(:mod:`bluefog_tpu.observe`):

* the :class:`~bluefog_tpu.observe.registry.MetricsRegistry` —
  counters (``bf_serving_requests_total``,
  ``bf_serving_retired_total{outcome=}``), windowed histograms
  (``bf_serving_ttft_seconds``, ``bf_serving_latency_seconds``), and
  per-step gauges, scrapeable as Prometheus text;
* the :class:`~bluefog_tpu.observe.tracer.Tracer` — one track per
  request (``admission -> prefill -> decode -> retire``), which the
  Chrome-trace timeline exports when started: load a timeline in
  chrome://tracing and the continuous-batching interleaving is visible
  directly — staggered prefills riding between decode steps — and one
  ``engine`` track with a ``step`` span per :meth:`ServingEngine.step`
  and its phases inside (:meth:`ServingMetrics.span`), which
  ``Tracer.span`` also writes into the profiler's trace as
  ``bf.engine.*``.

``summary()`` keeps its original dict shape (the operator dashboard the
serving tests and bench consume); ``BLUEFOG_OBSERVE=0`` stops the
registry/tracer publication while leaving the summary intact.

All timestamps come from the engine's injected clock, so tests drive
virtual time and percentiles are deterministic.
"""

from __future__ import annotations

import gc
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from bluefog_tpu import logging_util
from bluefog_tpu import timeline as timeline_mod
from bluefog_tpu.observe import compiles as obs_compiles
from bluefog_tpu.observe import registry as obs_registry
from bluefog_tpu.observe import tracer as obs_tracer
from bluefog_tpu.observe.registry import percentile  # noqa: F401  (moved
# to observe/registry.py; re-exported here for backward compatibility)

__all__ = ["ServingMetrics", "percentile"]

#: the track of the engine's own spans (``bf.engine.*`` in a profile)
ENGINE_TRACK = "engine"

#: a step is SLOW (one ``engine.slow_step`` instant, one WARNING line)
#: when it is longer than this multiple of the running median step AND
#: than the floor: the first decode step after a burst of prefill
#: drains the chunks queued on the device (150-224 ms where the median
#: is 7-20) and is no stall; a stall holds a step for seconds
SLOW_STEP_FACTOR = 20.0
SLOW_STEP_FLOOR_S = 0.5
#: the steps the running median is taken over, and the fewest that make
#: one (an engine's first steps compile its programs)
SLOW_STEP_WINDOW = 64
SLOW_STEP_MIN_HISTORY = 8
#: the spans inside ``token_fetch`` (where a tracer takes them): the
#: wait for the step's tokens, and their transfer and conversion
FETCH_PARTS = ("device_wait", "host_copy")


class _RequestRecord:
    __slots__ = ("submit_t", "admit_t", "first_token_t", "finish_t",
                 "n_tokens", "outcome", "tracer")

    def __init__(self, submit_t: float, tracer=None):
        self.submit_t = submit_t
        self.admit_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        self.n_tokens = 0
        self.outcome: Optional[str] = None
        # the tracer the request's spans BEGAN on, pinned at submit: a
        # BLUEFOG_OBSERVE flip or timeline stop mid-request must not
        # send the closing E records to a different tracer than the Bs
        # (same policy as context._timeline_open)
        self.tracer = tracer


class ServingMetrics:
    """Per-engine request records + publication into the global
    registry/tracer (opt out with ``BLUEFOG_OBSERVE=0``; pass an
    explicit ``registry=`` to isolate, e.g. per-test)."""

    def __init__(self, registry=None):
        self._req: Dict[object, _RequestRecord] = {}
        self._occupancy: List[float] = []
        self._queue_depth: List[int] = []
        self.n_rejected = 0
        self.n_failovers = 0
        self.last_step_ts: Optional[float] = None
        self._registry = registry
        # prefix-cache / prefill accounting
        self.n_prefill_chunks = 0
        self.n_prefix_chunks_restored = 0
        self.n_prefix_tokens_restored = 0
        # speculative decoding accounting
        self.n_spec_steps = 0
        self.n_spec_active = 0
        self.n_spec_emitted = 0
        # one-program-ahead accounting (``on_decode_step``, ``on_tokens``)
        self.n_decode_ahead = 0
        self.n_decode_overrun_slots = 0
        # the stat_expert_rows leaves as last read (``on_expert_rows``)
        self._expert_rows_seen = None
        # decoded tokens' expected exit passes, summed, and the tokens
        # (``on_exit_pdf``)
        self._exit_pass_sum = 0.0
        self._exit_pass_tokens = 0
        # the stalled step: the longest step so far, and what a step is
        # held against (``on_step``)
        self.n_steps = 0
        self.longest_step: Optional[dict] = None
        self._recent_steps: deque = deque(maxlen=SLOW_STEP_WINDOW)
        self._gc2_seen = gc.get_stats()[2]["collections"]
        self._compiles_seen = obs_compiles.backend_compiles()

    # -- observe plumbing --------------------------------------------- #
    def _reg(self):
        if self._registry is not None:
            return self._registry
        if not obs_registry.enabled():
            return None
        return obs_registry.get_registry()

    @property
    def publishing(self) -> bool:
        """Whether a registry takes what the engine counts: where none
        does, the engine skips the work that only feeds counters."""
        return self._reg() is not None

    def _tracer(self):
        return obs_tracer.effective_tracer(timeline_mod.get_timeline())

    def span(self, name: str, **args):
        """A span of the engine's own work (``step`` and its phases) on
        the ``engine`` track; yields the tracer's ``Span``."""
        # with no tracer (observe off, no timeline) the Span records
        # nothing and only takes its two stamps: the step's wall time
        # still feeds an explicit registry
        return obs_tracer.Span(self._tracer(), ENGINE_TRACK, name, args)

    def _span(self, rid, activity: Optional[str]):
        """Close the request's open span and (unless retiring) open the
        next lifecycle phase on its per-request track — on the tracer
        the request's spans began on."""
        rec = self._req.get(rid)
        tr = rec.tracer if rec is not None else None
        if tr is None:
            return
        track = f"request.{rid}"
        tr.end(track)
        if activity is not None:
            tr.begin(track, activity)

    # -- lifecycle events (engine calls these) ------------------------ #
    def on_submit(self, rid, now: float):
        tr = self._tracer()
        self._req[rid] = _RequestRecord(now, tracer=tr)
        if tr is not None:
            tr.begin(f"request.{rid}", "admission")
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_requests_total",
                        "requests submitted").inc()

    def on_reject(self, rid, now: float):
        self.n_rejected += 1
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_rejected_total",
                        "requests refused (backpressure or too long)").inc()

    def on_admit(self, rid, now: float):
        rec = self._req[rid]
        rec.admit_t = now
        self._span(rid, "prefill")
        reg = self._reg()
        if reg is not None:
            reg.histogram("bf_serving_queue_wait_seconds",
                          "submit -> slot").observe(now - rec.submit_t)

    def on_first_token(self, rid, now: float):
        rec = self._req[rid]
        rec.first_token_t = now
        rec.n_tokens += 1
        self._span(rid, "decode")
        reg = self._reg()
        if reg is not None:
            reg.histogram("bf_serving_ttft_seconds",
                          "submit -> first token").observe(
                              now - rec.submit_t)

    def on_token(self, rid, now: float):
        self._req[rid].n_tokens += 1

    def on_tokens(self, n_tokens: int, overrun: int = 0):
        """A decode step emitted ``n_tokens`` over all its slots (first
        tokens included): the counter moves once a step, not once a
        token.  ``overrun``: the slots the program advanced for a
        request that had left them by the time the host read it (an EOS
        in the program before, a cancel, a shed, while this one was in
        flight): device work that emitted nothing."""
        self.n_decode_overrun_slots += overrun
        reg = self._reg()
        if reg is not None and n_tokens:
            reg.counter("bf_serving_tokens_total",
                        "tokens generated").inc(n_tokens)
        if reg is not None and overrun:
            reg.counter(
                "bf_serving_decode_overrun_slots_total",
                "slots a decode program advanced whose request had "
                "retired while the program was in flight (the tokens "
                "are dropped)").inc(overrun)

    def on_retire(self, rid, now: float, outcome: str):
        rec = self._req[rid]
        rec.finish_t = now
        rec.outcome = outcome
        self._span(rid, "retire")
        self._span(rid, None)
        tr = rec.tracer
        if tr is not None:
            tr.instant(f"request.{rid}.{outcome}")
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_retired_total",
                        "requests retired", outcome=outcome).inc()
            reg.histogram("bf_serving_latency_seconds",
                          "submit -> retire").observe(now - rec.submit_t)

    def on_failover(self, rid, now: float):
        """``rid`` was handed off to another replica (replica death or
        graceful drain) — it retired HERE with outcome ``failover`` and
        resumes elsewhere with its tokens intact."""
        self.n_failovers += 1
        rec = self._req.get(rid)
        tr = rec.tracer if rec is not None else None
        if tr is not None:
            tr.instant(f"request.{rid}.failover")
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_failovers_total",
                        "requests handed off to another replica").inc()

    def on_prefill_chunk(self, n_tokens: int, rebuilt: int = 0,
                         streamed=(), mixed: int = 0, state: int = 0,
                         looped: int = 0):
        """One cold prefill chunk ran (a model forward over one chunk)
        with ``n_tokens`` valid positions; the rest of the chunk's
        width was padding.  Together with :meth:`on_prefix_restore`
        this splits prompt coverage into compute vs copy.  ``rebuilt``:
        the cached positions whose keys and values the chunk rebuilt
        from a latent before it attended, summed over layers (the
        model's ``rebuilt_positions``, from the lengths the host holds;
        0 and no counter for a model that reads its cache as it
        stands).  ``streamed``: ``((kind, rows), ...)``, the cache rows
        the chunk read to attend, summed over the kind's layers (the
        model's ``chunk_streamed_positions``, from the same lengths;
        empty for a model that declares none).  ``mixed``: see
        :meth:`on_mixed_tokens`.  ``state``: the chunk's valid tokens
        times the model's ``state_layers``, the tokens that went through
        a recurrent layer's chunked form (0: no counter).  ``looped``:
        see :meth:`on_loop_layer_tokens`."""
        self.n_prefill_chunks += 1
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_prefill_chunks_total",
                        "cold prefill chunks computed").inc()
            reg.counter("bf_serving_prefill_tokens_total",
                        "valid positions in cold prefill chunks"
                        ).inc(n_tokens)
            if rebuilt:
                reg.counter(
                    "bf_serving_latent_expanded_positions_total",
                    "cached positions whose keys and values prefill "
                    "chunks rebuilt from a latent, summed over layers"
                ).inc(rebuilt)
            for kind, rows in streamed:
                reg.counter(
                    "bf_serving_chunk_streamed_positions_total",
                    "cache rows prefill chunks read to attend, summed "
                    "over the layers of the kind", kind=kind).inc(rows)
            if state:
                reg.counter(
                    "bf_serving_state_chunk_tokens_total",
                    "valid tokens of prefill chunks times the layers "
                    "that keep a recurrent state").inc(state)
        self.on_mixed_tokens(mixed)
        self.on_loop_layer_tokens(looped)

    def on_decode_step(self, n_slots: int, attended=(), streamed=(),
                       mixed: int = 0, ahead: bool = False,
                       state: int = 0, looped: int = 0,
                       state_streamed: int = 0):
        """One decode program call (plain or speculative) advanced
        ``n_slots`` active slots: slots / steps is the batch size a
        decode step.  ``attended``: ``((kind, positions), ...)``, the
        cache positions the step's queries attended by kind of layer
        (``protocol.attended_positions``), from the lengths the host
        holds; ``streamed``: the positions the program fetched to
        attend them (``ServedModel.streamed_positions``, every slot of
        the pool), from the same lengths.  With ``decode_horizon`` > 1
        both are the call's first token step.  ``mixed``: see
        :meth:`on_mixed_tokens`.  ``ahead``: the program was dispatched
        while the one before it was in flight (the host had not read
        its tokens): the share of such calls is how often the engine's
        host work ran beside the device's.  ``state``: the slots that
        decoded times the model's ``state_layers``, the single-token
        steps of a recurrent state (0: no counter).  ``state_streamed``:
        the slots whose state the program READ times those layers (the
        model's ``state_streamed_steps``: the decoding slots under a
        kernel over the live rows, the pool's capacity under the XLA
        step; over ``state`` it is 1.0 where the kernel engages).
        ``looped``: see :meth:`on_loop_layer_tokens`."""
        self.n_decode_ahead += bool(ahead)
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_decode_steps_total",
                        "decode program calls").inc()
            if ahead:
                reg.counter(
                    "bf_serving_decode_ahead_total",
                    "decode program calls dispatched while the call "
                    "before was in flight").inc()
            reg.counter("bf_serving_decode_slots_total",
                        "active slots summed over decode program calls"
                        ).inc(n_slots)
            for kind, positions in attended:
                reg.counter(
                    "bf_serving_attended_positions_total",
                    "cache positions decode steps attended, summed over "
                    "slots and the layers of the kind", kind=kind
                ).inc(positions)
            for kind, positions in streamed:
                reg.counter(
                    "bf_serving_streamed_positions_total",
                    "cache positions decode steps fetched, summed over "
                    "every slot of the pool and the layers of the kind",
                    kind=kind).inc(positions)
            if state:
                reg.counter(
                    "bf_serving_state_steps_total",
                    "decoding slots of decode program calls times the "
                    "layers that keep a recurrent state").inc(state)
            if state_streamed:
                reg.counter(
                    "bf_serving_state_streamed_steps_total",
                    "slots whose recurrent state decode program calls "
                    "read times the layers that keep one"
                ).inc(state_streamed)
        self.on_mixed_tokens(mixed)
        self.on_loop_layer_tokens(looped)

    def on_loop_layer_tokens(self, looped: int):
        """A call ran ``looped`` = live tokens x passes x layers layer
        applications (a model that declares ``loop_steps`` > 1: the
        valid tokens of a chunk, the slots a step decodes; padding runs
        on the device too and is not counted).  0: no counter."""
        reg = self._reg()
        if reg is not None and looped:
            reg.counter(
                "bf_serving_loop_layer_tokens_total",
                "live tokens times the passes they make times the layers "
                "of a pass (a looped stack), over prefill chunks and "
                "decode steps").inc(looped)

    def on_loop_steps(self, steps: int):
        """A token of the served model makes ``steps`` passes through
        its layers."""
        reg = self._reg()
        if reg is not None:
            reg.gauge("bf_serving_loop_steps",
                      "passes a token makes through the served model's "
                      "layers (total_ut_steps)").set(steps)

    def on_exit_pdf(self, pdfs, slots):
        """A decode program call of a looped model: ``pdfs``, its
        ``stat_exit_pdf`` leaves (one ``[capacity, 1, passes]`` array:
        the exit gate's distribution over the passes for each slot's
        LAST token), and the ``slots`` that decoded.  Keeps the mean,
        over the decoded tokens so far, of the expected exit pass
        ``sum_t t p_t`` (passes count from 1): what an exit below the
        served threshold of 1 would leave of the loop."""
        reg = self._reg()
        if reg is None or not len(pdfs) or not len(slots):
            return
        pdf = np.asarray(pdfs[0], np.float64)[np.asarray(slots)]
        pdf = pdf.reshape(-1, pdf.shape[-1])
        self._exit_pass_sum += float(
            (pdf * np.arange(1, pdf.shape[-1] + 1)).sum())
        self._exit_pass_tokens += pdf.shape[0]
        reg.gauge(
            "bf_serving_exit_pass_mean",
            "mean over decoded tokens of the pass the exit gate expects "
            "a token to leave at (sum_t t p_t); every token is served "
            "every pass").set(self._exit_pass_sum / self._exit_pass_tokens)

    def on_mixed_tokens(self, mixed: int):
        """A call mixed ``mixed`` = live tokens x sublayers of residual
        streams (a model that declares ``mixed_sublayers``: the valid
        tokens of a chunk, the slots a step decodes; padding is mixed
        on the device too and not counted).  0: no counter."""
        reg = self._reg()
        if reg is not None and mixed:
            reg.counter(
                "bf_hc_mixed_tokens_total",
                "live tokens times the sublayers their residual streams "
                "were mixed around (hyper-connections), over prefill "
                "chunks and decode steps").inc(mixed)

    def on_residual_streams(self, streams: int):
        """The served model's residual path has ``streams`` streams."""
        reg = self._reg()
        if reg is not None:
            reg.gauge("bf_hc_streams",
                      "streams of the served model's residual path "
                      "(hc_mult)").set(streams)

    def on_pool(self, cache_bytes: dict, capacity: int = 1):
        """The slot pool of ``capacity`` slots was built: ``{"full" |
        "window" | "state": bytes}`` it reserves
        (``SlotPool.cache_bytes``)."""
        reg = self._reg()
        if reg is not None:
            for kind, nbytes in cache_bytes.items():
                reg.gauge(
                    "bf_serving_cache_bytes",
                    "bytes the slot pool reserves, by kind of cache leaf "
                    "(full: max_len positions; window: a ring; state: a "
                    "recurrent layer's memory)",
                    kind=kind).set(nbytes)
            if "state" in cache_bytes:
                reg.gauge(
                    "bf_serving_state_bytes_per_slot",
                    "bytes of recurrent state one slot holds, whatever "
                    "its length").set(cache_bytes["state"] // capacity)

    def on_expert_groups(self, groups: int, kept: int):
        """The served model chooses a token's experts inside the best
        ``kept`` of ``groups`` groups of the router's outputs."""
        reg = self._reg()
        if reg is not None:
            reg.gauge("bf_moe_groups_kept",
                      "groups of router outputs a token's experts are "
                      "chosen in").set(kept)
            reg.gauge("bf_moe_groups",
                      "groups the router's outputs fall in").set(groups)

    def on_expert_choices(self, chosen, slots, held):
        """A decode program call of a model with expert layers:
        ``chosen``, one ``[capacity, top_k]`` array of expert ids a
        layer (its ``stat_experts`` leaf: what each slot's LAST token
        chose, so with ``decode_horizon`` h the counters sample one
        token in h: their ratios hold, their totals are 1/h), the
        ``slots`` that decoded, and ``held = (first, count)``, the
        experts this share holds.  Counts the assignments that fell on
        held experts and on absent ones, and the held experts hit (the
        ones the step's expert loop read), a layer a step."""
        reg = self._reg()
        if reg is None or not len(slots):
            return
        first, count = held
        slots = np.asarray(slots)
        n_held = n_absent = n_hit = n_layers = 0
        for ids in chosen:
            ids = np.asarray(ids)[slots].reshape(-1)
            mine = ids[(ids >= first) & (ids < first + count)]
            n_held += mine.size
            n_absent += ids.size - mine.size
            n_hit += np.unique(mine).size
            n_layers += 1
        for label, n in (("true", n_held), ("false", n_absent)):
            reg.counter(
                "bf_moe_assignments_total",
                "token-to-expert assignments of decode steps, by whether "
                "this share holds the expert", held=label).inc(n)
        reg.counter(
            "bf_moe_experts_hit_total",
            "held experts chosen by at least one token, summed over "
            "expert layers and decode steps").inc(n_hit)
        reg.counter(
            "bf_moe_layer_steps_total",
            "expert layers times decode steps").inc(n_layers)

    def on_expert_rows(self, totals):
        """``totals``: a model's ``stat_expert_rows`` leaves as a decode
        step brought them, one ``[capacity, 2]`` array a layer: the rows
        the layer's expert matmuls have computed and the held
        assignments they were computed for, summed over every call that
        wrote the slot (prefill chunks and decode steps alike; a decode
        step's joint count lands on one slot).  Counts what they grew
        by since the last reading; a total that fell was zeroed with
        its slot (or wrapped) and counts from nothing."""
        reg = self._reg()
        if reg is None or not len(totals):
            return
        now = np.stack([np.asarray(t).astype(np.uint32).astype(np.int64)
                        for t in totals])
        seen = self._expert_rows_seen
        self._expert_rows_seen = now
        if seen is not None:
            now = np.where(now >= seen, now - seen, now)
        rows, assigned = now.reshape(-1, 2).sum(0)
        reg.counter(
            "bf_moe_expert_rows_total",
            "rows the held experts' matmuls computed (loop turns x rows "
            "a turn), over expert layers and calls").inc(int(rows))
        reg.counter(
            "bf_moe_expert_assignments_total",
            "held token-to-expert assignments of the calls that "
            "bf_moe_expert_rows_total counts (prefill and decode)"
            ).inc(int(assigned))

    def on_prefix_restore(self, rid, n_chunks: int, n_tokens: int):
        """``n_chunks`` cached K/V chunks (``n_tokens`` prompt tokens)
        were copied into ``rid``'s slot instead of being prefilled."""
        if n_chunks <= 0:
            return
        self.n_prefix_chunks_restored += n_chunks
        self.n_prefix_tokens_restored += n_tokens
        rec = self._req.get(rid)
        tr = rec.tracer if rec is not None else None
        if tr is not None:
            tr.instant(f"request.{rid}.prefix_restore[{n_chunks}]")
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_prefix_chunks_restored_total",
                        "prompt chunks admitted from the prefix cache"
                        ).inc(n_chunks)
            reg.counter("bf_serving_prefix_tokens_restored_total",
                        "prompt tokens admitted from the prefix cache"
                        ).inc(n_tokens)

    def on_spec_step(self, n_active: int, n_emitted: int):
        """One speculative decode step over ``n_active`` slots emitted
        ``n_emitted`` tokens total: the accepted-tokens-per-step ratio
        speculation is judged by, in :meth:`summary`.  The registry has
        it already — per-token accounting flows through
        :meth:`on_tokens` and the step's slots through
        :meth:`on_decode_step`, so it is ``bf_serving_tokens_total`` over
        ``bf_serving_decode_slots_total``."""
        self.n_spec_steps += 1
        self.n_spec_active += n_active
        self.n_spec_emitted += n_emitted

    def on_step(self, occupancy: float, queue_depth: int,
                step_seconds: Optional[float] = None,
                now: Optional[float] = None,
                phases: Optional[Dict[str, float]] = None,
                decoding: int = 0):
        """One engine step ended.  ``phases``: the seconds of each of
        its phases, summed by the engine from the stamps its spans hold
        (``device_wait`` and ``host_copy`` lie inside ``token_fetch``
        where a tracer took the spans); ``decoding``: the slots it
        decoded."""
        self._occupancy.append(occupancy)
        self._queue_depth.append(queue_depth)
        if now is not None:
            # the replica's liveness heartbeat (engine-clock seconds):
            # the fleet router's staleness guard compares this against
            # its own clock — a replica that stops stepping stops
            # advancing it and goes suspect after BLUEFOG_REPLICA_STALE_S
            self.last_step_ts = now
        reg = self._reg()
        if reg is not None:
            reg.counter("bf_serving_steps_total", "engine steps").inc()
            reg.gauge("bf_serving_slot_occupancy",
                      "active slots / capacity, last step").set(occupancy)
            reg.gauge("bf_serving_queue_depth",
                      "queued requests, last step").set(queue_depth)
            if now is not None:
                reg.gauge("bf_serving_last_step_ts",
                          "engine-clock time of the last step").set(now)
            if step_seconds is not None:
                # the engine's measured step wall time, in the SAME
                # histogram family the train loop reports into — the
                # per-rank step-time signal the fleet gossip
                # (observe.fleet.collect_local) aggregates
                reg.histogram("bf_step_wall_seconds",
                              "train/engine step wall time",
                              loop="serving").observe(step_seconds)
        if step_seconds is not None:
            self._on_step_seconds(step_seconds, phases or {}, decoding, reg)
        self.n_steps += 1

    def _on_step_seconds(self, seconds: float, phases: Dict[str, float],
                         decoding: int, reg) -> None:
        """The stalled step: keep the longest step's record, and say so
        once, where it happens, when a step is SLOW (the constants at
        the top of the module) with the phase that held it and what the
        interpreter did since the step before."""
        gc2 = gc.get_stats()[2]["collections"]
        compiles = obs_compiles.backend_compiles()
        gc2_since = gc2 - self._gc2_seen
        compiles_since = compiles - self._compiles_seen
        self._gc2_seen, self._compiles_seen = gc2, compiles
        recent = self._recent_steps
        longest = self.longest_step
        # a step that compiled is long for a reason that has its own
        # account (bf_compile_seconds_total) and is not the record
        if not compiles_since and (longest is None
                                   or seconds > longest["seconds"]):
            self.longest_step = {
                "seconds": seconds, "phases": dict(phases),
                "step": self.n_steps, "decoding": decoding,
                "chunk": "prefill_chunk" in phases}
            if reg is not None:
                gone = set(longest["phases"]) - set(phases) if longest else ()
                for phase, value in (("step", seconds), *phases.items(),
                                     *((p, 0.0) for p in gone)):
                    reg.gauge("bf_serving_longest_step_seconds",
                              "the longest engine step that compiled "
                              "nothing, and the seconds of each of its "
                              "phases", phase=phase).set(value)
        if seconds >= SLOW_STEP_FLOOR_S \
                and len(recent) >= SLOW_STEP_MIN_HISTORY:
            median = float(np.median(recent))
            if seconds > SLOW_STEP_FACTOR * median:
                self._on_slow_step(seconds, median, phases, decoding,
                                   gc2_since, compiles_since)
        recent.append(seconds)

    def _on_slow_step(self, seconds, median, phases, decoding, gc2,
                      compiles) -> None:
        split = " ".join(f"{k}={v:.4f}" for k, v in phases.items())
        # the innermost span that held it: a part of token_fetch where
        # the parts were taken (token_fetch then stands for what they
        # leave of it), ``self`` for what no phase covers
        own = dict(phases)
        parts = sum(own.get(part, 0.0) for part in FETCH_PARTS)
        own["self"] = seconds - (sum(own.values()) - parts)
        if parts:
            own["token_fetch"] = own.get("token_fetch", 0.0) - parts
        held = max(own, key=own.get)
        tr = self._tracer()
        if tr is not None:
            tr.instant("engine.slow_step", ENGINE_TRACK, step=self.n_steps,
                       seconds=seconds, held=held, gc2=gc2,
                       compiles=compiles, **phases)
        logging_util.get_logger().warning(
            "engine.slow_step step=%d seconds=%.4f median=%.4f held=%s "
            "decoding=%d chunk=%d gc2=%d compiles=%d phases: %s",
            self.n_steps, seconds, median, held, decoding,
            int("prefill_chunk" in phases), gc2, compiles, split)

    # -- summaries ----------------------------------------------------- #
    def ttfts(self) -> List[float]:
        return [r.first_token_t - r.submit_t for r in self._req.values()
                if r.first_token_t is not None]

    def latencies(self) -> List[float]:
        return [r.finish_t - r.submit_t for r in self._req.values()
                if r.finish_t is not None]

    def summary(self) -> dict:
        """One dict with the operator dashboard: percentile latencies,
        aggregate tokens/s over the active window, mean occupancy/queue
        depth, and outcome counts."""
        recs = list(self._req.values())
        finished = [r for r in recs if r.finish_t is not None]
        tokens = sum(r.n_tokens for r in recs)
        if finished:
            t0 = min(r.submit_t for r in recs)
            t1 = max(r.finish_t for r in finished)
            window = max(t1 - t0, 1e-12)
        else:
            window = 0.0
        outcomes: Dict[str, int] = {}
        for r in recs:
            if r.outcome:
                outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
        ttft = self.ttfts()
        lat = self.latencies()
        prefix_total = self.n_prefill_chunks + self.n_prefix_chunks_restored
        return {
            "n_requests": len(recs),
            "n_finished": len(finished),
            "n_rejected": self.n_rejected,
            "n_failovers": self.n_failovers,
            "outcomes": outcomes,
            "tokens_generated": tokens,
            "tokens_per_sec": (tokens / window) if window else 0.0,
            "ttft_p50": percentile(ttft, 50),
            "ttft_p99": percentile(ttft, 99),
            "latency_p50": percentile(lat, 50),
            "latency_p99": percentile(lat, 99),
            "mean_slot_occupancy": (float(np.mean(self._occupancy))
                                    if self._occupancy else 0.0),
            "mean_queue_depth": (float(np.mean(self._queue_depth))
                                 if self._queue_depth else 0.0),
            "max_queue_depth": (int(np.max(self._queue_depth))
                                if self._queue_depth else 0),
            "prefill_chunks": self.n_prefill_chunks,
            "prefix_chunks_restored": self.n_prefix_chunks_restored,
            "prefix_tokens_restored": self.n_prefix_tokens_restored,
            # restored / (restored + computed): how much prompt coverage
            # the prefix cache turned from forwards into copies
            "prefix_hit_rate": ((self.n_prefix_chunks_restored
                                 / prefix_total) if prefix_total else 0.0),
            "spec_steps": self.n_spec_steps,
            # tokens emitted per active slot-step: > 1 means speculation
            # is paying for its draft passes
            "accepted_per_step": ((self.n_spec_emitted
                                   / self.n_spec_active)
                                  if self.n_spec_active else 0.0),
            # the process's longest step: seconds, the seconds of each
            # phase, its index, the slots it decoded, whether it held a
            # prefill chunk (None before the first timed step)
            "longest_step": self.longest_step,
        }
