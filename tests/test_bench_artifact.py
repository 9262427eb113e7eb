"""bench.py's output contract: whenever the battery ends, the last stdout
line is a complete, parseable summary, and an unfinished battery exits
non-zero.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_artifact_contract_under_budget_kill():
    # a battery whose total budget expires almost immediately still
    # prints a complete parseable summary as the LAST stdout line —
    # every section present as a real record or an explicit pending
    # record — and exits non-zero: it did not finish
    import json

    env = dict(os.environ)
    env["BENCH_TOTAL_BUDGET_S"] = "78"  # guard fires ~3 s in
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=70, env=env, cwd=REPO,
    )
    assert res.returncode == 1, res.stderr[-500:]
    lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
    final = json.loads(lines[-1])  # last line parses, whatever happened
    assert final["metric"].startswith("shallow_water")
    assert "battery_note" in final and "budget" in final["battery_note"]
    metrics = final["metrics"]
    assert len(metrics) >= 9  # every planned section is represented
    for m in metrics:
        assert "metric" in m
        assert "value" in m  # real value or explicit null + error reason
        if m["value"] is None:
            assert m.get("error"), m


def test_artifact_contract_sigterm():
    # SIGTERM (the driver's timeout signal) must flush the full summary
    import json
    import signal as _signal
    import time as _time

    env = dict(os.environ)
    env["BENCH_TOTAL_BUDGET_S"] = "3000"
    env["JAX_PLATFORMS"] = "cpu"
    import threading

    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO,
    )
    try:
        # wait (bounded) for the startup summary — the contract says it
        # exists from second zero — so the signal lands after the
        # handler is installed even on a loaded host
        first_box = []
        reader = threading.Thread(
            target=lambda: first_box.append(proc.stdout.readline()),
            daemon=True)
        reader.start()
        reader.join(timeout=60)
        assert first_box and first_box[0].strip(), "no startup summary"
        _time.sleep(1)
        proc.send_signal(_signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    lines = [first_box[0]] + [ln for ln in out.splitlines() if ln.strip()]
    final = json.loads(lines[-1])
    assert final["metric"].startswith("shallow_water")
    assert "signal" in final.get("battery_note", "")
    assert len(final["metrics"]) >= 9
    assert proc.returncode == 1
