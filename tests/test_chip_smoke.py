"""chip_smoke.py's phases at ``LlamaConfig.tiny`` sizes on CPU devices.

The script itself has no CPU branch and no size option: ``main`` fixes
the 1B widths and the ``tpu`` platform.  These tests call the phase
functions directly and replace the script's three chip-only names
(``PLATFORM``, ``on_chip_path``, ``memory_stat``) — on CPU devices Pallas
kernels interpret, so no program holds a ``tpu_custom_call``, ``"auto"``
decode resolves to XLA, and the allocator keeps no statistics.  What
stays checked is everything else the phases assert: finite moving
losses, flash-vs-xla loss parity, request completion and token parity
with ``llama_generate``, stable jit caches, the atc step against the
NumPy mixing, the exact one-peer mean, the collectives in the module.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bluefog_tpu import models

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke_module():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def smoke(smoke_module, monkeypatch):
    """The module steered onto CPU devices; ``smoke.chip_facts`` collects
    what the chip-only checks were asked."""
    facts = []
    monkeypatch.setattr(smoke_module, "PLATFORM", "cpu")
    monkeypatch.setattr(smoke_module, "on_chip_path",
                        lambda ok, what: facts.append((bool(ok), what)))
    monkeypatch.setattr(smoke_module, "memory_stat", lambda dev, key: 1)
    monkeypatch.setattr(smoke_module, "chip_facts", facts, raising=False)
    return smoke_module


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_unsteered_script_refuses_a_cpu(smoke_module, monkeypatch, capsys):
    """As the driver's sandbox runs it: JAX finds only the CPU, the
    device phase exits non-zero and no result line is printed.  (The
    cache helper is kept from re-pointing this session's JAX.)"""
    monkeypatch.setattr(smoke_module, "configure_compilation_cache",
                        lambda: "/cache")
    with pytest.raises(SystemExit) as exc:
        smoke_module.main([])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_device_phase_counts_chips(smoke, monkeypatch, capsys):
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    assert smoke.check_device(4, "/cache") == four
    out = capsys.readouterr().out
    assert "cpu cpu x4" in out and "/cache" in out and jax.__version__ in out
    with pytest.raises(SystemExit) as exc:
        smoke.check_device(1, "/cache")
    assert "needs 1 chip" in str(exc.value.code)


def test_one_chip_phases_at_tiny_size(smoke, capsys):
    cfg = models.LlamaConfig.tiny(dtype=jnp.bfloat16)
    devices = jax.devices()[:1]
    smoke.train_phase(cfg, devices, batch=2, seq=32)
    smoke.serve_phase(cfg, devices, capacity=4, max_len=64,
                      prefill_chunk=8, n_requests=6, prompt_len=(4, 30),
                      new_tokens=(3, 8))
    print(smoke.result_line(devices))
    assert _last_line(capsys) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": devices[0].device_kind,
                   "count": 1}}
    # the chip-only facts were asked, and are what a CPU gives: no
    # kernel in either program, "auto" resolved to xla
    assert [ok for ok, _ in smoke.chip_facts] == [False, False, False]


def test_four_chip_phase_at_tiny_size(smoke, capsys):
    cfg = models.LlamaConfig.tiny(dtype=jnp.bfloat16)
    devices = jax.devices()[:4]
    smoke.exchange_phase(cfg, devices, batch=2, seq=32, parity_layers=1,
                         full_layers=2)
    print(smoke.result_line(devices))
    assert _last_line(capsys)["device"]["count"] == 4
    assert smoke.chip_facts and not any(ok for ok, _ in smoke.chip_facts)


def test_a_failed_check_exits_nonzero(smoke_module):
    with pytest.raises(SystemExit) as exc:
        smoke_module.on_chip_path(False, "no kernel")
    assert "no kernel" in str(exc.value.code)


def test_bfrun_parent_imports_initialize_no_backend():
    """``bfrun``'s parent imports the package and must leave the chip to
    its children: one process per chip.  A fresh interpreter (this one
    already runs JAX) imports what the launcher and the chip scripts
    import and must end with no backend created."""
    import subprocess
    import sys

    code = (
        "import bluefog_tpu, bluefog_tpu.run.run, bluefog_tpu.serving, "
        "bluefog_tpu.models, bluefog_tpu.benchutil\n"
        "from bluefog_tpu.config import configure_compilation_cache\n"
        "configure_compilation_cache()\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, xb._backends\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=REPO, timeout=120)


def test_compilation_cache_directory(monkeypatch):
    """Unset: ``<checkout>/.jax_cache``, a fixed path.  Set: the
    variable's directory, and the helper touches nothing."""
    from bluefog_tpu import config

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert config.configure_compilation_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert config.configure_compilation_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
