"""chip_smoke.py rehearsed on the CPU at a tiny size.

The script's phases run here with the same checks as on the chip (float64
reference bound, batched service requests, CG true residuals, shard
placement); only the sizes shrink.  Without a TPU, ``main`` must refuse to
run and print no result.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(rand_rows=4096, rand_nnz=25, aniso_n=48, poisson_n=32)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod       # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_checked_holds_results_to_the_bound(smoke):
    a = smoke.matrices(smoke.Sizes(**TINY), 0)["aniso"]
    x = smoke.operand(np.random.default_rng(0), a.shape[1], 2)
    ref = smoke.reference(a, x)
    assert smoke.checked(ref[0].astype(np.float32), ref, "f32 copy") < 1e-6
    bad = ref[0].copy()
    bad[3, 1] += 1e-4 * ref[1][3, 1]
    with pytest.raises(smoke.SmokeFailure, match="1 entries outside"):
        smoke.checked(bad, ref, "perturbed")
    bad[5, 0] = np.nan
    with pytest.raises(smoke.SmokeFailure, match="2 entries outside"):
        smoke.checked(bad, ref, "nan")


def test_apply_phase_checks_every_format(smoke, capsys):
    mats = smoke.matrices(smoke.Sizes(**TINY), 0)
    smoke.apply_phase(mats, 0, smoke.CompileClock())
    out = capsys.readouterr().out
    # random: auto, ell, coo; aniso: auto, ell, bsr, coo; x2 directions x2 nv
    assert out.count("max_err=") == (3 + 4) * 2 * 2
    assert "tpu_custom_call=True" not in out    # interpret mode on the CPU


def test_service_phase_batches_and_solves(smoke, capsys):
    sizes = smoke.Sizes(**TINY)
    smoke.service_phase(smoke.matrices(sizes, 0)["random"], sizes, 0)
    out = capsys.readouterr().out
    assert "4 requests in one batched apply" in out
    assert out.count("true_rel_residual=") == 2


@pytest.mark.multidev
def test_chips4_phase_on_four_host_devices():
    code = (
        "import sys; sys.path.insert(0, {root!r}); import chip_smoke as s; "
        "sz = s.Sizes(**{tiny!r}); "
        "s.chips4_phase(s.matrices(sz, 0), 0, s.CompileClock()); "
        "print('CHIPS4 OK')").format(root=str(ROOT), tiny=TINY)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "CHIPS4 OK" in proc.stdout
    assert proc.stdout.count("plan shards on 4 distinct devices") == 8
    assert proc.stdout.count("max_err=") == 2 * 4 * 2


def test_interpret_mode_follows_the_platform():
    from repro.compat import interpret_mode
    assert interpret_mode() is True             # JAX_PLATFORMS=cpu here
    assert interpret_mode(False) is False       # compile rehearsals
    assert interpret_mode(True) is True


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, tmp_path):
    import jax
    from repro.compat import use_compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache("/checkout") == str(tmp_path)
    assert calls == []                          # jax reads the variable
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert use_compile_cache("/checkout") == "/checkout/.jax_cache"
    assert calls == [("jax_compilation_cache_dir", "/checkout/.jax_cache")]
