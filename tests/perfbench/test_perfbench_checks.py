"""The output check holds: with the timed path broken underneath,
``correct`` comes out false; the control (the reference in the
precision below the configuration's, in the program's place) comes out
as not correct; and the arithmetic the check rests on.  Tiny sizes, CPU
devices, the harness's look for a chip skipped."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import clocks, loader

from conftest import TINY_DECODER, TINY_RESNET, TINY_TRAFFIC, add_cell

SEED = 2**31 + 4242   # more than 32 signed bits hold


# ------------------------------------------------------------------ #
# the timed path broken underneath: correct must come out false
# ------------------------------------------------------------------ #
def run_cell(root, name, seconds=0.3):
    cell = loader.load_cell(name, root)
    chips = jax.devices()[:cell.chips]
    return cell.runner().run(cell, SEED, seconds, False, chips,
                             clocks.Spans(), clocks.now(), "/unused")


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        bench_copy, on_cpu, monkeypatch, capsys):
    from bluefog_tpu.optim import functional as F

    real = F.build_train_step

    def build(*args, **kwargs):
        step = real(*args, **kwargs)

        def stuck(params, opt_state, batch, i):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            _, _, loss = step(copy(params), copy(opt_state), batch, i)
            return params, opt_state, loss

        stuck.lower, stuck.jitted = step.lower, step.jitted
        return stuck

    monkeypatch.setattr(F, "build_train_step", build)
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-train",
             TINY_TRAFFIC["tiny-train"])
    result = run_cell(bench_copy, "cell")
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert "update_norm_gap = 1 " in out and "<-- over" in out


def test_a_step_that_leaves_out_the_exchange_is_not_correct(
        bench_copy, on_cpu, monkeypatch, capsys):
    from bluefog_tpu.optim import functional as F

    real = F.build_train_step

    def build(loss_fn, opt, mesh, **kwargs):
        kwargs.pop("schedule", None)
        return real(loss_fn, opt, mesh, **dict(kwargs, comm_mode="none"))

    monkeypatch.setattr(F, "build_train_step", build)
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-train-atc",
             TINY_TRAFFIC["tiny-train-atc"], chips=4)
    result = run_cell(bench_copy, "cell")
    assert result["correct"] is False
    assert "update_norm_gap" in capsys.readouterr().out


def test_a_step_that_mixes_with_the_wrong_peer_is_not_correct(
        bench_copy, on_cpu, monkeypatch, capsys):
    """What the norms cannot see: every rank averages with a rank, at
    the right weight, but with the next round's."""
    from bluefog_tpu import topology

    real = topology.one_peer_dynamic_schedule

    def rotated(n):
        rounds = real(n)
        return rounds[1:] + rounds[:1]

    monkeypatch.setattr(topology, "one_peer_dynamic_schedule", rotated)
    traffic = dict(TINY_TRAFFIC["tiny-train-atc"])
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-train-atc", traffic,
             chips=4)
    result = run_cell(bench_copy, "cell")
    out = capsys.readouterr().out
    assert result["correct"] is False
    line = next(x for x in out.splitlines() if "mix_abs_gap" in x)
    assert "<-- over" in line
    # an update's size (the learning rate is 1e-3), not a rounding's
    assert float(line.split("= ")[1].split()[0]) > 1e-4


def test_a_part_of_the_batch_left_out_is_not_correct(
        bench_copy, on_cpu, monkeypatch):
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-train",
             TINY_TRAFFIC["tiny-train"])
    cell = loader.load_cell("cell", bench_copy)
    family = cell.family()
    real = family.train_loss

    def half(sz, traffic):
        loss_fn, has_aux = real(sz, traffic)
        return (lambda p, b: loss_fn(p, b[:1])), has_aux

    monkeypatch.setattr(family, "train_loss", half)
    assert run_cell(bench_copy, "cell")["correct"] is False


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        bench_copy, on_cpu, monkeypatch, capsys):
    from bluefog_tpu.serving import engine

    real = engine._decode_step_prog
    vocab = TINY_DECODER["vocab_size"]

    def altered(*args, **kwargs):
        pool, hist = real(*args, **kwargs)
        return pool, (hist + 1) % vocab

    altered._cache_size = real._cache_size
    monkeypatch.setattr(engine, "_decode_step_prog", altered)
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-serve",
             TINY_TRAFFIC["tiny-serve"])
    result = run_cell(bench_copy, "cell", seconds=1.0)
    assert result["failed"] == 0 and result["correct"] is False
    assert "check: logit_gap" in capsys.readouterr().out


# ------------------------------------------------------------------ #
# the control: the reference in the precision below, in the program's
# place, has to come out as not correct
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("mix", ["tiny-train", "tiny-images"])
def test_the_control_of_a_training_cell_is_not_correct(bench_copy, on_cpu,
                                                       mix):
    config = TINY_RESNET if mix == "tiny-images" else TINY_DECODER
    add_cell(bench_copy, "cell", config, mix, TINY_TRAFFIC[mix])
    cell = loader.load_cell("cell", bench_copy)
    runner = cell.runner()
    devices = jax.devices()[:1]
    want = runner.reference_readings(cell, SEED, devices)
    control = runner.reference_readings(cell, SEED, devices, control=True)
    numbers, ok = runner.compare(control, want, cell.traffic["limits"])
    assert not ok
    job = runner.Job(cell, SEED, devices, clocks.Spans())
    sound, ok = runner.compare(runner.program_readings(job), want,
                               cell.traffic["limits"])
    assert ok
    assert numbers["grad_norm_gap"][0] > 3 * sound["grad_norm_gap"][0]
    # a control that still learns: rounding, not a severed gradient
    assert numbers["grad_norm_gap"][0] < 0.5
    assert numbers["update_norm_gap"][0] < 0.5


def test_the_control_of_the_serving_cell_reads_a_wider_gap(bench_copy,
                                                           on_cpu):
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-serve",
             TINY_TRAFFIC["tiny-serve"])
    cell = loader.load_cell("cell", bench_copy)
    runner = cell.runner()
    spans = clocks.Spans()
    server = runner.Server(cell, SEED, spans)
    due, prompts, outputs = runner.schedule(cell.traffic, 1.0)
    requests = runner.make_requests(server.sz, prompts, outputs, SEED)
    runner.drive(server, requests, due, 1.0, 30.0)
    sample = list(range(len(requests)))
    sound, read = runner.logit_gaps(cell, server.sz, server.params,
                                    requests, sample)
    control, _ = runner.logit_gaps(cell, server.sz, server.params,
                                   requests, sample, control=True)
    limit = cell.traffic["limits"]["logit_gap"]["limit"]
    assert read == sum(len(r.tokens) for r in requests)
    assert sound <= limit < control


def test_the_moments_are_found_in_adam_and_sgd_states():
    import optax

    from perfbench.runners import train

    params = {"a": jnp.ones((2, 3)), "b": {"c": jnp.ones((4,))}}
    grads = jax.tree.map(lambda x: 2.0 * x, params)
    for opt, scale, count in ((optax.adamw(1e-3, b1=0.9), 10.0, 2),
                              (optax.sgd(0.1, momentum=0.9), 1.0, 1)):
        _, state = opt.update(grads, opt.init(params), params)
        found = train.moment_trees(state, params)
        assert len(found) == count
        assert jax.tree.structure(found[0]) == jax.tree.structure(params)
        assert np.allclose(np.asarray(found[0]["a"]) * scale, 2.0)


@pytest.mark.parametrize("spec", [
    {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95,
     "eps": 1e-8, "weight_decay": 0.1},
    {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9}])
def test_a_written_out_optimizer_is_what_optax_computes(spec):
    """``optimizers/<name>.py`` against ``optax.<name>`` of the same
    keys, three steps, and the first gradient back from the first
    moment."""
    from perfbench.runners import train

    rule = loader.load_module(loader.ROOT, "optimizers", spec["name"])
    opt = train.program_optimizer(spec)
    rs = np.random.RandomState(3)
    p = rs.randn(5, 7)
    want, state = jnp.asarray(p, jnp.float32), None
    state = opt.init(want)
    m, v = np.zeros_like(p), np.zeros_like(p)
    for t in (1, 2, 3):
        g = rs.randn(5, 7)
        updates, state = opt.update(jnp.asarray(g, jnp.float32), state, want)
        want = want + updates
        m, v = rule.moments(spec, g, m, v)
        if t == 1:
            assert m * rule.first_gradient_scale(spec) == pytest.approx(g)
        p = rule.apply(spec, p, m, v, t)
        assert np.asarray(want) == pytest.approx(p, abs=2e-6)


def test_worst_leaf_gap_floors_small_leaves_at_the_median_leaf():
    from perfbench.harness import reference_train as rt

    want = [np.array([1.0]), np.array([2.0]), np.array([1e-9])]
    got = [np.array([1.1]), np.array([2.0]), np.array([2e-9])]
    # the all-but-zero leaf is held against the median leaf's norm (1.0)
    assert rt.worst_leaf_gap(got, want) == pytest.approx(0.1)
    got[2] = np.array([0.5])
    assert rt.worst_leaf_gap(got, want) == pytest.approx(0.5)
    # leaves with no gradient at all do not drag the floor to zero
    want += [np.array([0.0])] * 5
    assert rt.worst_leaf_gap(got + [np.array([0.0])] * 5,
                             want) == pytest.approx(0.5)
    assert rt.worst_leaf_gap(got + [np.array([0.3])] * 5,
                             want) == pytest.approx(0.5)
    assert rt.worst_leaf_gap([np.array([0.1])], [np.array([0.0])]) \
        == float("inf")


def test_exchange_matrices_are_row_stochastic_one_peer_rounds():
    from perfbench.harness import reference_train as rt

    one_peer = loader.load_module(loader.ROOT, "exchanges", "one_peer_exp2")
    rounds = one_peer.matrices(4)
    assert len(rounds) == 2 and one_peer.MIXES == "parameters"
    for k, w in enumerate(rounds):
        assert np.allclose(w.sum(1), 1.0) and np.allclose(w.sum(0), 1.0)
        for r in range(4):
            assert w[r, r] == 0.5 and w[r, (r - 2 ** k) % 4] == 0.5
    # two rounds reach the exact mean on four ranks
    assert np.allclose(rounds[1] @ rounds[0], 0.25)
    none = loader.load_module(loader.ROOT, "exchanges", "none")
    assert np.array_equal(none.matrices(4)[0], np.eye(4))
    # one rank mixes with nobody, whatever the exchange
    assert np.array_equal(rt.rounds_of(one_peer, 1)[0], np.eye(1))


def test_mix_gap_reads_a_wrong_peer_weight_or_order_at_an_update_s_size():
    """Hand-made: four ranks whose updates differ, mixed by round 0 of
    the one-peer exchange in float32."""
    from perfbench.harness import reference_train as rt

    spec = {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9}
    rule = loader.load_module(loader.ROOT, "optimizers", "sgd")
    w0, w1 = loader.load_module(
        loader.ROOT, "exchanges", "one_peer_exp2").matrices(4)
    rs = np.random.RandomState(5)
    before = [np.repeat(rs.randn(1, 6), 4, 0).astype(np.float32)]
    m = [rs.randn(4, 6).astype(np.float32)]
    local = before[0] - np.float32(0.1) * m[0]
    sound = [(w0.astype(np.float32) @ local).astype(np.float32)]
    args = (before, sound, m, m, 1)
    assert rt.mix_gap(spec, rule, w0, *args) < 1e-6
    assert rt.mix_gap(spec, rule, w1, *args) > 1e-2          # wrong peer
    skew = 0.6 * np.eye(4) + 0.4 * (w0 - 0.5 * np.eye(4)) / 0.5
    assert rt.mix_gap(spec, rule, skew, *args) > 1e-3        # wrong weight
    assert rt.mix_gap(spec, rule, np.eye(4), *args) > 1e-2   # no exchange
    # mixed before the update instead of after it: the seeded parameters
    # are one and the same on every rank, so nothing is mixed at all
    assert rt.mix_gap(spec, rule, w0, before, [local], m, m, 1) > 1e-2
