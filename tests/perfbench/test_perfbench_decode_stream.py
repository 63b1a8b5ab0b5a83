"""``decode_cache_streamed_pct`` (PR 27): the reader on counters set by
hand, on a program that does not count (the parent of the PR that added
the counter) and off the chip; and on the counters of a tiny engine
under both attention lowerings, where the XLA lowering reads 100 by
construction and the bounded kernel less."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu import models, observe
from bluefog_tpu.observe import MetricsRegistry
from bluefog_tpu.serving import Request, ServingEngine
from perfbench.harness import loader
from perfbench.harness import program_trace as pt

from conftest import REPO

CELL = "mistral7b-serve-steady"
NAME = "decode_cache_streamed_pct"


def _reader():
    return loader.load_cell(CELL, REPO).layer_metric(NAME)


def test_the_cell_lists_the_metric_under_the_decode_layer():
    cell = loader.load_cell(CELL, REPO)
    entry = {m["name"]: m for m in cell.per_layer}[NAME]
    assert entry["layer"] == "decode" and entry["moves"] == "itl_p95_ms"
    assert entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["workloads"] == [CELL]


def test_the_share_of_counters_set_by_hand(monkeypatch):
    cell = loader.load_cell(CELL, REPO)
    eng = cell.traffic["engine"]
    ctx = {"serve": {}, "traffic": cell.traffic,
           "sizes": {"num_hidden_layers": 16}}
    reserved = eng["capacity"] * eng["max_len"] * 16
    counters = {"bf_serving_decode_steps_total": 10.0,
                "bf_serving_streamed_positions_total": 2.0 * reserved}
    monkeypatch.setattr(pt, "counter_value",
                        lambda name, **labels: counters.get(name))
    read = _reader().reduce
    assert read(None, None, ctx) is None            # off the chip
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    assert read(None, None, ctx) == pytest.approx(20.0)
    assert read(None, None, {"traffic": cell.traffic}) is None  # no serve
    del counters["bf_serving_streamed_positions_total"]
    assert read(None, None, ctx) is None     # a program that does not count
    counters.clear()
    assert read(None, None, ctx) is None


@pytest.mark.parametrize("decode_attn", ["xla", "pallas"])
def test_the_share_of_a_tiny_engines_own_counters(monkeypatch, decode_attn):
    reg = MetricsRegistry()
    monkeypatch.setattr(observe, "get_registry", lambda: reg)
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, max_seq_len=1024)
    variables = models.Llama(cfg).init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 4), jnp.int32))
    engine = dict(capacity=3, max_len=1024, prefill_chunk=64)
    eng = ServingEngine(variables, cfg, decode_attn=decode_attn,
                        registry=reg, **engine)
    rs = np.random.RandomState(3)
    for n, new in ((40, 6), (530, 5)):
        eng.submit(Request(rs.randint(0, 256, (n,)).astype(np.int32), new))
    eng.run()
    ctx = {"serve": {}, "traffic": {"engine": engine},
           "sizes": {"num_hidden_layers": cfg.n_layers}}
    share = _reader().reduce(None, None, ctx)
    if decode_attn == "xla":
        assert share == pytest.approx(100.0)
    else:
        # blocks of 512 in 1024 positions: a live row streams one or
        # two, an idle one none
        assert 100.0 / 6 <= share <= 50.0
