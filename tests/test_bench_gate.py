"""The bench regression gate (``benchutil.bench_compare`` /
``bench_regression_gate``): exit 1 on a regressed record, exit 0 on a
before/after pair that did not regress, over the shapes of record this
repo emits.  The records are built here: a gate's baseline is a record
taken on the same installation, and none is committed for this one yet.
"""

import copy
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.perf


def _load(name):
    with open(os.path.join(REPO, name)) as fh:
        return json.load(fh)


def _bench_line(value=2700.0, mfu=0.33):
    """A ``bench.py`` output line."""
    return {"metric": "resnet50_train_images_per_sec_per_chip",
            "value": value, "unit": "img/s/chip", "vs_baseline": 10.0,
            "mfu": mfu, "flops_per_step_per_device": 3.0e12,
            "peak_tflops_per_chip": 197.0}


def _driver_record(line):
    """The wrapper a driver run stores the line in."""
    return {"n": 1, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(line) + "\n", "parsed": line}


def _audit_record():
    """The 8B audit's sections (benchmarks/llama_8b_overlap.py) with the
    fields the gate reads."""
    return {
        "epilogue": {"claims": {"cost_bytes_not_above_r11": True}},
        "hierarchical": {
            "flat": {"dcn_bytes_per_step": 4.0e9,
                     "tp_overlap_fraction": 0.85},
            "hierarchical": {"dcn_bytes_per_step": 2.0e9,
                             "tp_overlap_fraction": 0.85},
            "dcn_bytes_per_step": 2.0e9,
            "tp_overlap_fraction": 0.85,
            "claims": {"dcn_bytes_cut": True, "dcn_bytes_ratio": 0.5,
                       "tp_overlap_defended": True},
        },
        "compressed": {
            "dcn_bytes_per_step": 0.75e9,
            "claims": {"dcn_bytes_vs_int8_only": 0.375,
                       "dcn_bytes_halved": True},
        },
    }


@pytest.fixture
def baseline_path(tmp_path):
    path = tmp_path / "bench_baseline.json"
    path.write_text(json.dumps(_bench_line()))
    return str(path)


def test_headline_reads_the_driver_wrapper_like_the_raw_line():
    """A driver record holds the bench line under ``"parsed"``; the gate
    reads the same headline from either shape."""
    from bluefog_tpu.benchutil import bench_headline

    line = _bench_line()
    head = bench_headline(_driver_record(line))
    assert head == bench_headline(line)
    assert head["value"] == line["value"] and head["mfu"] == line["mfu"]


def test_gate_exits_nonzero_on_synthetic_regression(baseline_path, capsys):
    """A 20% throughput/MFU drop beyond the 5% tolerance fails the
    gate (bench.py exits 1 on a False gate result)."""
    from bluefog_tpu.benchutil import bench_regression_gate

    regressed = _bench_line()
    regressed["value"] *= 0.8
    regressed["mfu"] *= 0.8
    ok = bench_regression_gate(regressed, baseline_path)
    assert ok is False
    out = capsys.readouterr().out
    assert "REGRESSED" in out


def test_gate_passes_on_before_after_pair(baseline_path, capsys):
    """A before/after pair of driver records inside the tolerance (a
    0.3% improvement) passes the gate: exit 0."""
    from bluefog_tpu.benchutil import bench_compare

    before = _driver_record(_bench_line(2738.2, 0.3343))
    after = _driver_record(_bench_line(2746.5, 0.3353))
    ok, rows = bench_compare(after, before)
    assert ok is True
    assert rows and not any(r["regressed"] for r in rows)
    # and the fresh record gates clean against a baseline file
    from bluefog_tpu.benchutil import bench_regression_gate

    assert bench_regression_gate(after, baseline_path) is True


def test_bench_py_defaults_to_committed_baseline():
    """A plain ``python bench.py`` (the driver's invocation) compares
    with nothing — no baseline is committed, a record from another
    installation is not one — and ``--compare`` names a record."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert bench.parse_args([]).compare is None
    assert not os.path.exists(
        os.path.join(REPO, "benchmarks", "bench_baseline.json"))
    assert bench.parse_args(["--compare", "x.json"]).compare == "x.json"


# --------------------------------------------------------------------- #
# serving + fleet-serving baselines (ISSUE 9): the two serving benches
# gate against committed records by default, same flow as bench.py
# --------------------------------------------------------------------- #
def _load_bench_module(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "benchmarks", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_bench_defaults_to_committed_baseline():
    """serving_bench.py gates against benchmarks/serving_baseline.json
    (the committed r07 record) by default; ``--compare ''`` opts out."""
    sb = _load_bench_module("serving_bench")
    args = sb.parse_args([])
    assert args.compare == sb.DEFAULT_BASELINE
    assert os.path.exists(args.compare)
    assert sb.parse_args(["--compare", ""]).compare is None
    assert sb.parse_args(["--compare", "x.json"]).compare == "x.json"


def test_serving_baseline_is_the_r07_record():
    base = _load(os.path.join("benchmarks", "serving_baseline.json"))
    r07 = _load("serving_bench_r07.json")
    assert base == r07
    assert base["continuous"]["tokens_per_sec"] > 0
    # the gate sees the serving headline fields
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(base)
    assert "continuous.tokens_per_sec" in head
    assert "continuous.ttft_p50" in head


def test_fleet_serving_defaults_and_baseline():
    """fleet_serving.py follows the same gate flow, and its committed
    baseline passed every machine-checked claim."""
    fs = _load_bench_module("fleet_serving")
    args = fs.parse_args([])
    assert args.compare == fs.DEFAULT_BASELINE
    assert os.path.exists(args.compare)
    assert fs.parse_args(["--compare", ""]).compare is None
    base = _load(os.path.join("benchmarks",
                              "fleet_serving_baseline.json"))
    assert all(base["machine_checked"].values())
    assert base["fleet_two"]["fleet_speedup"] > 1.0
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(base)
    assert "fleet_two.fleet_speedup" in head
    assert "prefix.hit_rate" in head
    assert "speculative.accepted_per_step" in head


def test_gate_catches_fleet_regression(capsys):
    """A collapsed fleet speedup / prefix hit rate fails the gate."""
    from bluefog_tpu.benchutil import bench_compare

    base = _load(os.path.join("benchmarks",
                              "fleet_serving_baseline.json"))
    regressed = copy.deepcopy(base)
    regressed["fleet_two"]["fleet_speedup"] = 1.0
    regressed["prefix"]["hit_rate"] *= 0.5
    ok, rows = bench_compare(regressed, base, tolerance=0.25)
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "fleet_two.fleet_speedup" in bad
    assert "prefix.hit_rate" in bad


# --------------------------------------------------------------------- #
# chaos-resilience baseline (ISSUE 10): the chaos bench joins the same
# rolling-baseline gate flow, with the preempt->rejoin record included
# --------------------------------------------------------------------- #
def test_chaos_bench_defaults_and_baseline():
    """chaos_resilience.py gates against the committed r13 artifact by
    default; ``--compare ''`` opts out; the committed record passed
    every machine-checked claim including the rejoin cycle."""
    cr = _load_bench_module("chaos_resilience")
    args = cr.parse_args([])
    assert args.compare == cr.DEFAULT_BASELINE
    assert os.path.exists(args.compare)
    assert cr.parse_args(["--compare", ""]).compare is None
    assert cr.parse_args(["--compare", "x.json"]).compare == "x.json"
    base = _load(os.path.join("benchmarks", "chaos_resilience_r13.json"))
    assert all(base["checks"].values())
    rejoin = base["rejoin"]
    assert rejoin["recompiles"] == 0
    assert rejoin["final_membership_all_live"]
    assert rejoin["post_rejoin_floor"] <= 1e-12
    assert rejoin["sim"]["grow_byte_equal"]
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(base)
    assert "rejoin.throughput_recovery" in head
    assert "rejoin.post_rejoin_floor" in head


# --------------------------------------------------------------------- #
# hierarchical-exchange audit (ISSUE 11): the 8B audit's flat-vs-
# two-level section joins the gate flow — DCN bytes/step is a gated
# lower-is-better headline, so a schedule change that silently re-
# inflates the inter-machine wire fails the compare
# --------------------------------------------------------------------- #
@pytest.mark.hier
def test_hierarchical_audit_fields_are_gated():
    """The gate reads the hierarchical audit's headline fields out of
    its section, and an audit record gates clean against itself."""
    from bluefog_tpu.benchutil import bench_compare, bench_headline

    base = _audit_record()
    head = bench_headline(base)
    assert head["hierarchical.dcn_bytes_per_step"] == 2.0e9
    assert head["hierarchical.tp_overlap_fraction"] == 0.85
    # booleans and nested legs are not headlines
    assert not any(k.startswith("epilogue.") for k in head)
    assert "hierarchical.dcn_bytes_cut" not in head
    ok, rows = bench_compare(base, base)
    assert ok is True and rows


@pytest.mark.hier
def test_gate_catches_dcn_byte_regression(capsys):
    """A schedule change that re-inflates the inter-machine wire (DCN
    bytes/step back up toward the flat exchange) fails the gate —
    lower is better for dcn_bytes_per_step."""
    from bluefog_tpu.benchutil import bench_compare

    base = _audit_record()
    regressed = copy.deepcopy(base)
    regressed["hierarchical"]["dcn_bytes_per_step"] *= 2.0
    regressed["hierarchical"]["tp_overlap_fraction"] *= 0.5
    ok, rows = bench_compare(regressed, base, tolerance=0.25)
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "hierarchical.dcn_bytes_per_step" in bad
    assert "hierarchical.tp_overlap_fraction" in bad
    # ... and a halved wire is an improvement, never a failure
    better = copy.deepcopy(base)
    better["hierarchical"]["dcn_bytes_per_step"] *= 0.5
    ok2, _ = bench_compare(better, base)
    assert ok2 is True


def test_gate_catches_rejoin_regression(capsys):
    """A blown consensus floor / collapsed throughput recovery after
    rejoin fails the gate."""
    from bluefog_tpu.benchutil import bench_compare

    base = _load(os.path.join("benchmarks", "chaos_resilience_r13.json"))
    regressed = copy.deepcopy(base)
    regressed["rejoin"]["post_rejoin_floor"] = 1e-3
    regressed["rejoin"]["throughput_recovery"] = 0.1
    ok, rows = bench_compare(regressed, base, tolerance=0.5)
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "rejoin.post_rejoin_floor" in bad
    assert "rejoin.throughput_recovery" in bad


# --------------------------------------------------------------------- #
# chaos-serving baseline (ISSUE 14): replica death, token-exact
# failover, and drain join the gate flow — lost_requests is a gated
# lower-is-better headline with ZERO tolerance, so even one request
# silently dropped by a future failover change fails the compare
# --------------------------------------------------------------------- #
def test_chaos_serving_defaults_and_baseline():
    """chaos_serving.py gates against the committed r15 artifact by
    default; ``--compare ''`` opts out; the committed record passed
    every machine-checked claim: zero lost requests, bit-exact
    failover, bounded TTFT degradation, (N-1)/N throughput recovery,
    and zero recompiles under every fault pattern."""
    cs = _load_bench_module("chaos_serving")
    args = cs.parse_args([])
    assert args.compare == cs.DEFAULT_BASELINE
    assert os.path.exists(args.compare)
    assert cs.parse_args(["--compare", ""]).compare is None
    assert cs.parse_args(["--compare", "x.json"]).compare == "x.json"
    base = _load(os.path.join("benchmarks", "chaos_serving_r15.json"))
    assert all(base["machine_checked"].values())
    assert base["recompiles"] == 0
    chaos = base["chaos_serving"]
    assert chaos["lost_requests"] == 0
    assert chaos["bitwise_exact"] and chaos["suspect_detected"]
    assert chaos["failovers"] > 0
    assert (chaos["throughput_recovery"]
            >= base["config"]["recovery_floor"])
    assert base["drain"]["lost_requests"] == 0
    assert base["drain"]["flushed_chunks"] > 0
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(base)
    assert "chaos_serving.lost_requests" in head
    assert "chaos_serving.throughput_recovery" in head
    assert "fault_free.ttft_p99" in head
    assert "drain.lost_requests" in head


def test_gate_catches_lost_request_regression(capsys):
    """A failover change that strands even ONE request fails the gate
    at zero tolerance (lower-is-better, 0 -> 1 is an infinite relative
    regression), as does a collapsed recovery ratio."""
    from bluefog_tpu.benchutil import bench_compare

    base = _load(os.path.join("benchmarks", "chaos_serving_r15.json"))
    regressed = copy.deepcopy(base)
    regressed["chaos_serving"]["lost_requests"] = 1
    regressed["chaos_serving"]["throughput_recovery"] = 0.2
    ok, rows = bench_compare(regressed, base, tolerance=0.25,
                             tolerances={
                                 "chaos_serving.lost_requests": 0.0})
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "chaos_serving.lost_requests" in bad
    assert "chaos_serving.throughput_recovery" in bad
    # ... and the committed record gates clean against itself
    ok2, _ = bench_compare(base, base)
    assert ok2 is True

# --------------------------------------------------------------------- #
# adaptive-topology baseline (ISSUE 15): the closed-loop control plane
# joins the gate flow — step_time_ratio (lower-better) and
# cost_to_consensus_advantage (higher-better) are gated headlines, so
# a control-plane change that stops adapting (ratios collapse to 1.0)
# fails the compare
# --------------------------------------------------------------------- #
def test_adaptive_topology_defaults_and_baseline():
    """chaos_adaptive_topology.py gates against the committed r16
    artifact by default; ``--compare ''`` opts out; the committed
    record passed every machine-checked claim: trigger->swap->commit
    under congestion AND shrink with zero recompiles, probation
    rollback restoring the incumbent, and the straggler named."""
    at = _load_bench_module("chaos_adaptive_topology")
    args = at.parse_args([])
    assert args.compare == at.DEFAULT_BASELINE
    assert os.path.exists(args.compare)
    assert at.parse_args(["--compare", ""]).compare is None
    assert at.parse_args(["--compare", "x.json"]).compare == "x.json"
    base = _load(os.path.join("benchmarks",
                              "chaos_adaptive_topology_r16.json"))
    assert all(base["checks"].values())
    assert base["adaptation"]["step_time_ratio"] < 0.9
    assert base["adaptation"]["cost_to_consensus_advantage"] > 1.05
    assert base["congested"]["recompiles"] == 0
    assert base["shrink"]["recompiles_adapted"] == 0
    assert base["rollback"]["restored"] == "initial"
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(base)
    assert "adaptation.step_time_ratio" in head
    assert "adaptation.cost_to_consensus_advantage" in head


def test_gate_catches_no_adaptation_regression(capsys):
    """A control plane that silently stops re-planning (post-swap step
    time no better than the congested incumbent, cost-to-consensus
    advantage gone) fails the gate on BOTH headline directions."""
    from bluefog_tpu.benchutil import bench_compare

    base = _load(os.path.join("benchmarks",
                              "chaos_adaptive_topology_r16.json"))
    regressed = copy.deepcopy(base)
    regressed["adaptation"]["step_time_ratio"] = 1.0
    regressed["adaptation"]["cost_to_consensus_advantage"] = 1.0
    regressed["congested"]["step_time_ratio"] = 1.0
    regressed["congested"]["cost_to_consensus_advantage"] = 1.0
    ok, rows = bench_compare(regressed, base, tolerance=0.25)
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "adaptation.step_time_ratio" in bad
    assert "adaptation.cost_to_consensus_advantage" in bad
    # ... and the committed record gates clean against itself
    ok2, _ = bench_compare(base, base)
    assert ok2 is True

# --------------------------------------------------------------------- #
# compressed-mixing baseline (ISSUE 17): the EF top-k audit joins the
# gate flow — compressed.dcn_bytes_per_step is a gated lower-is-better
# headline, so an encoder change that silently re-inflates the sparse
# wire (k drift, mask packing, scale width) fails the compare
# --------------------------------------------------------------------- #
@pytest.mark.hier
def test_compressed_audit_fields_are_gated():
    """The gate reads the compressed-mixing audit's DCN bytes/step out
    of its section, beside the hierarchical leg's in the same record."""
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(_audit_record())
    assert head["compressed.dcn_bytes_per_step"] == 0.75e9
    assert (head["compressed.dcn_bytes_per_step"]
            <= 0.5 * head["hierarchical.dcn_bytes_per_step"])


# --------------------------------------------------------------------- #
# fleet-sim baseline (ISSUE 17, simulator): the n=1024 virtual-time
# scenarios join the gate flow — every headline is deterministic (no
# wall-clock measurement feeds any gated figure), and
# sim_serving.lost_requests is gated at ZERO tolerance: the trace is
# seeded, so any drift in the loss count is a routing-behavior change,
# not noise
# --------------------------------------------------------------------- #
@pytest.mark.sim
def test_fleet_sim_defaults_and_baseline():
    """fleet_sim.py gates against the committed r20 artifact by
    default; ``--compare ''`` opts out; the committed record passed
    every machine-checked claim: congested-link trigger->swap->commit
    at n=1024, the preempted rank round-tripped through the real
    membership controller, the straggler named, token-exact replica
    failover mid-million-request trace, flash-crowd backpressure
    bounded, and (r20) every recorded decision replayed to the same
    winner/cost/margin with a deterministic chain digest."""
    fs = _load_bench_module("fleet_sim")
    args = fs.parse_args([])
    assert args.compare == fs.DEFAULT_BASELINE
    assert os.path.exists(args.compare)
    assert fs.parse_args(["--compare", ""]).compare is None
    assert fs.parse_args(["--compare", "x.json"]).compare == "x.json"
    base = _load(os.path.join("benchmarks", "fleet_sim_r20.json"))
    assert all(base["checks"].values())
    assert base["sim_training"]["step_time_ratio"] < 0.9
    assert base["sim_training"]["detect_to_swap_s"] > 0
    assert base["sim_serving"]["lost_requests"] >= 0
    assert base["sim_serving"]["tokens_per_sec"] > 0
    detail = base["sim_training_detail"]
    assert detail["ranks"] == 1024
    assert detail["flagged_stragglers"] == [33]
    assert detail["dead_at_end"] == 0
    serve = base["sim_serving_detail"]
    assert serve["requests"] == 1_000_000
    assert serve["failovers"] > 0
    assert serve["completed"] + serve["lost_requests"] == serve["requests"]
    # r20: the flight recorder rode along — decisions were replayed
    # against the recorded telemetry and every one re-scored to the
    # same winner; two same-seed runs produced the same chain digest
    assert base["replay"]["decisions_replayed"] >= 3
    assert base["replay"]["mismatches"] == 0
    replay = base["replay_detail"]
    assert len(replay["decision_chain_digest"]) == 64
    assert replay["train_decisions_recorded"] > 0
    assert replay["mix_decisions_recorded"] > 0
    assert replay["serve_decisions_retained"] <= fs.BLACKBOX_CAPACITY
    assert replay["recorder_overhead_pct"] < 2.0
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(base)
    assert "sim_training.step_time_ratio" in head
    assert "sim_training.detect_to_swap_s" in head
    assert "sim_serving.tokens_per_sec" in head
    assert "sim_serving.lost_requests" in head
    assert "replay.decisions_replayed" in head
    assert "replay.mismatches" in head


@pytest.mark.sim
def test_gate_catches_sim_regression(capsys):
    """A simulator change that slows detection, stops adapting, strands
    requests, or breaks decision replay fails the gate: detect_to_swap_s
    and step_time_ratio are lower-is-better, and lost_requests and
    replay.mismatches are pinned at zero tolerance — even a single extra
    lost request or a single decision that re-scores to a different
    winner regresses."""
    from bluefog_tpu.benchutil import bench_compare

    base = _load(os.path.join("benchmarks", "fleet_sim_r20.json"))
    regressed = copy.deepcopy(base)
    regressed["sim_training"]["step_time_ratio"] = 1.0
    regressed["sim_training"]["detect_to_swap_s"] *= 3.0
    regressed["sim_serving"]["lost_requests"] += 1
    regressed["replay"]["mismatches"] += 1
    ok, rows = bench_compare(
        regressed, base, tolerance=0.02,
        tolerances={"sim_serving.lost_requests": 0.0,
                    "replay.mismatches": 0.0})
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "sim_training.step_time_ratio" in bad
    assert "sim_training.detect_to_swap_s" in bad
    assert "sim_serving.lost_requests" in bad
    assert "replay.mismatches" in bad
    # ... and the committed record gates clean against itself
    ok2, _ = bench_compare(base, base,
                           tolerances={
                               "sim_serving.lost_requests": 0.0,
                               "replay.mismatches": 0.0})
    assert ok2 is True


@pytest.mark.hier
def test_gate_catches_compressed_wire_regression(capsys):
    """A change that doubles the compressed wire (e.g. shipping dense
    int8 where the top-k payload should be) fails the gate — lower is
    better for compressed.dcn_bytes_per_step."""
    from bluefog_tpu.benchutil import bench_compare

    base = _audit_record()
    regressed = copy.deepcopy(base)
    regressed["compressed"]["dcn_bytes_per_step"] *= 2.0
    ok, rows = bench_compare(regressed, base, tolerance=0.25)
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "compressed.dcn_bytes_per_step" in bad
    # ... and the committed record gates clean against itself
    ok2, _ = bench_compare(base, base)
    assert ok2 is True


# --------------------------------------------------------------------- #
# moe-dispatch baseline (ISSUE 19): the compiled all-to-all joins the
# gate flow — moe.cost_to_dispatch and moe.dcn_bytes_per_step are gated
# lower-is-better headlines and moe.compiled_advantage higher-is-better,
# so a compiler change that silently hands the dispatch back to the
# naive fused round (advantage -> 1.0, bytes re-inflated) fails the
# compare
# --------------------------------------------------------------------- #
@pytest.mark.moe
def test_moe_dispatch_defaults_and_baseline():
    """moe_dispatch.py gates against the committed r19 artifact by
    default; ``--compare ''`` opts out; the committed record passed
    every machine-checked claim: compiled beats naive on
    cost-to-dispatch at the 4x DCN pod without violating the one-shot
    congestion bound, the measured dispatch is bit-identical to
    lax.all_to_all, the int8 wire quarters the DCN bytes, and the
    expert kill->heal cycle completed with zero recompiles."""
    md = _load_bench_module("moe_dispatch")
    args = md.parse_args([])
    assert args.compare == md.DEFAULT_BASELINE
    assert os.path.exists(args.compare)
    assert md.parse_args(["--compare", ""]).compare is None
    assert md.parse_args(["--compare", "x.json"]).compare == "x.json"
    base = _load(os.path.join("benchmarks", "moe_dispatch_r19.json"))
    assert all(base["checks"].values())
    moe = base["moe"]
    assert moe["cost_to_dispatch"] < moe["naive_cost_to_dispatch"]
    assert moe["compiled_advantage"] > 1.0
    assert moe["cost_to_dispatch"] >= moe["one_shot_lower_bound"] - 1e-9
    assert moe["dcn_bytes_per_step_int8"] == moe["dcn_bytes_per_step"] / 4
    assert base["heal"]["recompiles"] == 0
    assert base["measured"]["bit_identical_to_naive"] is True
    from bluefog_tpu.benchutil import bench_headline

    head = bench_headline(base)
    assert "moe.cost_to_dispatch" in head
    assert "moe.compiled_advantage" in head
    assert "moe.dcn_bytes_per_step" in head
    assert "measured.step_time_ratio" in head


@pytest.mark.moe
def test_gate_catches_dispatch_bytes_regression(capsys):
    """A synthetic dispatch-bytes regression — the compiler handing the
    wire back to the naive round (cost up, advantage gone, DCN bytes
    re-inflated) — fails the gate on all three headline directions."""
    from bluefog_tpu.benchutil import bench_compare

    base = _load(os.path.join("benchmarks", "moe_dispatch_r19.json"))
    regressed = copy.deepcopy(base)
    regressed["moe"]["cost_to_dispatch"] = (
        base["moe"]["naive_cost_to_dispatch"])
    regressed["moe"]["compiled_advantage"] = 1.0
    regressed["moe"]["dcn_bytes_per_step"] *= 2.0
    ok, rows = bench_compare(regressed, base, tolerance=0.05)
    assert ok is False
    bad = {r["name"] for r in rows if r["regressed"]}
    assert "moe.cost_to_dispatch" in bad
    assert "moe.compiled_advantage" in bad
    assert "moe.dcn_bytes_per_step" in bad
    # ... and the committed record gates clean against itself
    ok2, _ = bench_compare(base, base)
    assert ok2 is True
