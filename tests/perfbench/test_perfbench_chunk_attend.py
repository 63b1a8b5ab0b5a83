"""The three per-layer metrics of a prefill chunk's attention in the
cell whose model has window and full layers: the entries are appended
and move nothing before them; the two scope readers on a trace built by
hand; the streamed share against a count by hand; every reader reads
nothing off the chip, from a program that writes no such scope, and
from one that counts no such rows (the parent's)."""

import pytest

from perfbench.harness import loader
from perfbench.harness import program_trace as pt, trace as tr

from conftest import REPO

CELL = "trinity-large-serve-mixed-len"
LATENT = "mistral-small4-serve-long-prompt"
MS = 1e6
NEW_READERS = ("chunk_attn_ms.window", "chunk_attn_ms.full",
               "chunk_cache_streamed_pct")
# per_layer from PR 29's first entry on, as this PR found it
BEFORE = ("prefill_chunk_device_ms", "moe_tile_fill_pct",
          "attn_scope_ms.latent", "chunk_attn_ms.latent",
          "latent_cache_bytes_per_token")
COUNTER = "bf_serving_chunk_streamed_positions_total"


# ------------------------------------------------------------------ #
# the entries
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("name, cells", [
    ("prefill_chunk_device_ms",
     ["mistral7b-serve-steady", CELL, LATENT]),
    ("moe_tile_fill_pct", [CELL, LATENT]),
    ("chunk_attn_ms.latent", [LATENT]),
    *((name, [CELL]) for name in NEW_READERS),
])
def test_an_append_moves_nothing_of_the_entries_before_it(name, cells):
    """The form a later append keeps too: the names from PR 29's first
    on BEGIN with the ones known here, and an entry's cells begin with
    the cells it came with.  (``test_perfbench_mla_moe.py``'s test of
    this name compares the list's whole tail, so it is expected to fail
    from this append on: ``tests/conftest.py``.)"""
    bench = loader.load_benchmark(REPO)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(BEFORE[0])
    known = [*BEFORE, *NEW_READERS]
    assert names[at:at + len(known)] == known
    entry = bench["per_layer"][names.index(name)]
    assert entry["workloads"][:len(cells)] == cells


def test_the_new_metrics_are_the_cells_alone_and_move_the_token_gap():
    bench = loader.load_benchmark(REPO)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"], entry["better"]) == (
            "model", "itl_p95_ms", "lower")
    assert [entries[n]["source"] for n in NEW_READERS] == [
        "device_trace", "device_trace", "program_counter"]
    assert entries["chunk_cache_streamed_pct"]["unit"] == "%"
    # what reads a window or a full leaf is this cell's; what reads a
    # latent is the other's (subsets: a later append keeps them)
    names, latent = ({m["name"] for m in loader.load_cell(c, REPO).per_layer}
                     for c in (CELL, LATENT))
    assert {"attn_scope_ms.window", "attn_scope_ms.full",
            *NEW_READERS} <= names - latent
    assert set(BEFORE[2:]) <= latent - names


# ------------------------------------------------------------------ #
# the readers
# ------------------------------------------------------------------ #
def hand_trace():
    """Two whole prefill chunks (10-30, 50-66) around a decode step
    whose operations carry the same scopes, and a chunk the window cuts
    (95-105)."""
    ops = [("%while.3 = () while()", 10 * MS, 24 * MS),        # container
           ("%fusion.1 = f32[] fusion()", 10 * MS, 14 * MS),   # window
           ("%fusion.2 = f32[] fusion()", 14 * MS, 24 * MS),   # full
           ("%fusion.4 = f32[] fusion()", 24 * MS, 29 * MS),   # experts
           ("%fusion.1 = f32[] fusion()", 32 * MS, 40 * MS),   # decode step
           ("%fusion.1 = f32[] fusion()", 50 * MS, 56 * MS),
           ("%fusion.2 = f32[] fusion()", 56 * MS, 58 * MS),
           ("%fusion.5 = f32[] fusion()", 58 * MS, 65 * MS),   # no scope
           ("%fusion.2 = f32[] fusion()", 95 * MS, 99 * MS)]
    modules = [("jit__prefill_chunk_prog(3)", 10 * MS, 30 * MS),
               ("jit__decode_step_prog(7)", 32 * MS, 48 * MS),
               ("jit__prefill_chunk_prog(3)", 50 * MS, 66 * MS),
               ("jit__prefill_chunk_prog(3)", 95 * MS, 105 * MS)]
    tf_ops = {0: {
        "%fusion.1 = f32[] fusion()":
            "jit(f)/Afmoe/layer_1/attention/bf.attn.window/while/body/"
            "dot_general",
        "%fusion.2 = f32[] fusion()":
            "jit(f)/Afmoe/layer_3/attention/bf.attn.full/while/body/exp",
        "%fusion.4 = f32[] fusion()":
            "jit(f)/Afmoe/layer_2/moe/bf.moe.experts/dot_general",
        "%fusion.5 = f32[] fusion()": "jit(f)/Afmoe/norm/mul"}}
    trace = tr.Trace([tr.DeviceTrace(0, ops, modules)],
                     [("pb.trace_window", 0.0, 100 * MS)])
    return trace, tf_ops


def _context(cell):
    return {"serve": {}, "traffic": cell.traffic, "peaks": None,
            "reference": cell.reference(),
            "sizes": cell.family().sizes(cell.config, "serve")}


class Run:
    def __init__(self, tf_ops):
        self.tf_ops, self.kept = tf_ops, {}

    def keep(self, key, make):
        if key not in self.kept:
            self.kept[key] = make()
        return self.kept[key]


def test_the_scope_readers_on_a_trace_built_by_hand(monkeypatch):
    trace, tf_ops = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    run = Run(tf_ops)
    monkeypatch.setattr(pt, "for_run", lambda f: run)
    read = lambda name: cell.layer_metric(name).reduce(
        trace, None, _context(cell))
    # two whole chunks: the loop's body is counted, the loop is not
    assert read("chunk_attn_ms.window") == pytest.approx((4 + 6) / 2)
    assert read("chunk_attn_ms.full") == pytest.approx((10 + 2) / 2)
    # the decode step's readers see the decode step alone
    assert read("attn_scope_ms.window") == pytest.approx(8.0)


def test_the_streamed_share_against_a_count_by_hand(monkeypatch, capsys):
    cell = loader.load_cell(CELL, REPO)
    ctx = _context(cell)
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    # 10 chunks; each could read 4 rings of 4096 + 512 rows and one
    # full leaf of 16,384: 34,816 rows
    counters = {("bf_serving_prefill_chunks_total", ()): 10.0,
                (COUNTER, (("kind", "window"),)): 10 * 4 * 1536.0,
                (COUNTER, (("kind", "full"),)): 10 * 2048.0}
    monkeypatch.setattr(pt, "counter_value", lambda name, **labels:
                        counters.get((name, tuple(sorted(labels.items())))))
    reader = cell.layer_metric("chunk_cache_streamed_pct")
    assert reader.reduce(None, None, ctx) == pytest.approx(
        100.0 * (4 * 1536 + 2048) / 34816)
    assert "window 6144 of 18432, full 2048 of 16384 rows a chunk" \
        in capsys.readouterr().out
    # every row of every leaf, as the parent's lowering read them
    counters[(COUNTER, (("kind", "window"),))] = 10 * 4 * 4608.0
    counters[(COUNTER, (("kind", "full"),))] = 10 * 16384.0
    assert reader.reduce(None, None, ctx) == pytest.approx(100.0)


def test_the_readers_read_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    trace, _ = hand_trace()
    cell = loader.load_cell(CELL, REPO)
    ctx = _context(cell)
    read = lambda: [cell.layer_metric(name).reduce(trace, None, ctx)
                    for name in NEW_READERS]
    assert read() == [None, None, None]          # off the chip
    # on the chip: a program that writes no such scope, and a registry
    # that counts chunks and no rows of them (the parent's)
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    run = Run({0: {}})
    monkeypatch.setattr(pt, "for_run", lambda f: run)
    monkeypatch.setattr(pt, "counter_value", lambda name, **labels:
                        7.0 if name.endswith("chunks_total") else None)
    assert read() == [None, None, None]
    # a model with other scopes (the latent one) has neither reading
    latent = {0: {"%fusion.1 = f32[] fusion()":
                  "jit(f)/MlaMoe/layer_1/attention/bf.attn.latent/dot"}}
    run = Run(latent)
    assert read() == [None, None, None]
