"""Rehearsal of chip_smoke.py on the CPU: its phase functions at reduced
size with interpreted kernels, and its refusal to run without a TPU.

The script itself has no CPU path; these tests drive the same phase
functions it runs on the chip, so a wrong path, argument or control
flow shows up here before any chip time is spent."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402

REDUCED = ["--reduced", "--requests", "4", "--prompt-len", "8",
           "--max-new", "4", "--batch-slots", "4"]


def test_script_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_device_phase_refuses_cpu():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.check_device(1)


def test_kernel_phase_reduced():
    chip_smoke.check_kernels(get_config("smollm-135m").reduced(),
                             get_config("deepseek-v3-671b").reduced())


def test_serve_and_open_loop_phases_reduced():
    done = chip_smoke.serve_run(chip_smoke.SERVE_ARGV + REDUCED)
    assert len(done) == 4
    done = chip_smoke.serve_run(
        chip_smoke.OPEN_LOOP_ARGV + REDUCED + ["--arrival-rate", "50"])
    assert len(done) == 4


def test_plain_path_comparison_reduced():
    share = chip_smoke.compare_plain(chip_smoke.SERVE_ARGV + REDUCED,
                                     chip_smoke.PLAIN_ARGV + REDUCED)
    assert share == 1.0  # float32 at reduced size: the paths agree


@pytest.mark.mesh
@pytest.mark.parametrize("arch,mesh", [("smollm-135m", (4, 1)),
                                       ("mistral-nemo-12b", (1, 2))])
def test_mesh_phase_reduced(cpu_mesh, arch, mesh):
    share = chip_smoke.mesh_run(
        chip_smoke.SERVE_ARGV + ["--arch", arch] + REDUCED, None, mesh)
    assert share == 1.0


def test_tapped_engine_records_logits(capsys):
    """The comparison reads real engine logits: one prefill vector per
    request and the first decode tick of every request that decodes."""
    args = chip_smoke.serve.parse_args(chip_smoke.PLAIN_ARGV + REDUCED)
    cfg, serve_cfg = chip_smoke.serve.build_config(args)
    params = chip_smoke.model_lib.init_params(
        cfg, chip_smoke.jax.random.PRNGKey(0))
    reqs = chip_smoke.serve.make_requests(args, cfg)
    srv, outs = chip_smoke._serve_tapped(cfg, params, serve_cfg, reqs)
    assert sorted(srv.prefill_logits) == sorted(outs)
    for r in reqs:
        assert srv.prefill_logits[r.uid].shape == (cfg.vocab_size,)
        assert outs[r.uid][0] == int(np.argmax(srv.prefill_logits[r.uid]))
        if r.max_new > 1:
            assert outs[r.uid][1] == int(
                np.argmax(srv.decode_logits[r.uid]))
    assert "skip counters" in capsys.readouterr().out


def test_last_line_contract(monkeypatch, capsys):
    """main() ends with exactly the JSON device line."""
    from repro.launch import compile_cache

    class _Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 0}

    monkeypatch.setattr(chip_smoke, "check_device", lambda n: _Dev())
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "-")
    for name in ("check_kernels", "serve_run", "compare_plain"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: None)
    monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [_Dev()])
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the entry points use the fixed <checkout>/.jax_cache."""
    from repro.launch import compile_cache

    prev = chip_smoke.jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert chip_smoke.jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.abspath(os.path.join(ROOT, ".jax_cache"))
        assert compile_cache.use_compile_cache() == want
        assert chip_smoke.jax.config.jax_compilation_cache_dir == want
    finally:
        chip_smoke.jax.config.update("jax_compilation_cache_dir", prev)
