"""Launcher CLIs run end-to-end in subprocesses (runnability proof)."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_cli(args, timeout=420, ok=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m"] + args,
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    msg = f"stdout:\n{r.stdout[-2000:]}\nstderr:\n{r.stderr[-2000:]}"
    assert (r.returncode == 0) == ok, msg
    return r.stdout if ok else r.stderr


def test_train_cli_with_crash_and_resume(tmp_path):
    args = [
        "repro.launch.train", "--arch", "deepseek-7b", "--reduced",
        "--steps", "8", "--batch", "2", "--seq", "64", "--mesh", "1x1",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
    ]
    err1 = run_cli(args + ["--crash-at", "5"], ok=False)
    assert "[injected] node failure at step 5" in err1
    out2 = run_cli(args)
    assert "resumed_from=4" in out2
    assert "final_step=7" in out2


def test_serve_cli(tmp_path):
    out = run_cli(
        [
            "repro.launch.serve", "--arch", "mamba2-130m", "--reduced",
            "--requests", "3", "--batch-size", "2", "--max-new", "4",
            "--max-len", "64",
        ]
    )
    assert "served 3 requests" in out


def test_dryrun_cli_reduced_cell(tmp_path):
    """dryrun CLI on one small full-config cell (production mesh, cached-free)."""
    out = run_cli(
        [
            "repro.launch.dryrun", "--arch", "mamba2-130m", "--shape",
            "decode_32k", "--mesh", "single", "--out", str(tmp_path),
            "--no-resume",
        ],
        timeout=560,
    )
    assert "1 ok, 0 skipped, 0 errors" in out


def _serve_argv():
    return [
        "--arch", "deepseek-7b", "--reduced", "--requests", "3",
        "--batch-size", "2", "--max-new", "4", "--max-len", "64",
        "--scheduler", "continuous", "--chaos-step-fail", "1",
    ]


def test_serve_cli_exits_nonzero_when_requests_fail(monkeypatch):
    """Two consecutive injected step faults fail the step's rows; the
    launcher reports it through its exit status, not just a status line."""
    from repro.launch import serve

    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    schedule = serve.FaultPlan.fail_device_step
    monkeypatch.setattr(
        serve.FaultPlan, "fail_device_step",
        lambda self, step, times=1, note="": schedule(self, step, times=2),
    )
    with pytest.raises(SystemExit) as exc:
        serve.main(_serve_argv())
    assert "requests failed" in str(exc.value.code)


def test_serve_cli_propagates_non_injected_step_error(monkeypatch):
    """An error out of the mixed step that is not the injected StepFault
    (a compile refusal, an out-of-memory) is not retried or swallowed."""
    from repro.launch import serve

    def refuse(self, site):
        raise ValueError("device refused the step")

    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    monkeypatch.setattr(serve.FaultPlan, "raise_if", refuse)
    with pytest.raises(ValueError, match="refused"):
        serve.main(_serve_argv())
