"""``chip_smoke.py`` off the chip: it must refuse to pass, and its phases
must still run end to end when the test steers the platform checks."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=300)


def _no_ok_line(stdout: str) -> bool:
    return '"ok": true' not in stdout


def test_chip_smoke_fails_without_a_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert _no_ok_line(out.stdout)
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_outside_the_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert out.returncode != 0
    assert _no_ok_line(out.stdout)


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke as a module, cut to a tiny size, its TPU checks steered."""
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    monkeypatch.setattr(chip_smoke, "ROBUST04", chip_smoke.AdHocShape(
        topics=6, depth=200, judged=80))
    # depth > 512 keeps the padded document axis wide enough for top-k
    monkeypatch.setattr(chip_smoke, "MSMARCO_DEV", chip_smoke.DevSetShape(
        queries=20, depth=600, sample=12))
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda count: jax.devices()[:count])
    monkeypatch.setattr(chip_smoke, "expect_kernel", lambda text, where: None)
    monkeypatch.setattr(chip_smoke.runtime, "enable_compile_cache",
                        lambda: None)
    return chip_smoke


def test_chip_smoke_phases_rehearse_on_cpu(smoke, capsys):
    assert smoke.main(["--seed", "3"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for phase in ("[A ", "[B ", "[C ", "[D "):
        assert any(line.startswith(phase) for line in lines), phase
    assert "route=topk_kernel" in next(l for l in lines if l.startswith("[B"))
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["device"]["count"] == 1


def test_chip_smoke_catches_a_wrong_answer(smoke, monkeypatch, capsys):
    real = smoke.pure_eval.evaluate

    def shifted_reference(run, qrel, measures):
        out = real(run, qrel, measures)
        for vals in out.values():
            vals["map"] += 1e-3
        return out

    monkeypatch.setattr(smoke.pure_eval, "evaluate", shifted_reference)
    assert smoke.main([]) == 1
    assert _no_ok_line(capsys.readouterr().out)
