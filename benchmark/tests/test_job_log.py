"""The job driver's log reader on a recorded job log (a CPU rehearsal of
PR 22, cut to 24 tasks): window edges, the accounting, and a re-leased task."""

import os
import statistics

import pytest

from benchmark import common

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "job.log")
TRAFFIC = {"warmup_tasks": 2, "minibatch_size": 256}


@pytest.fixture(scope="module")
def job():
    return common.load_module("drivers", "job")


def _text():
    with open(FIXTURE) as f:
        return f.read()


def test_what_the_log_says(job):
    log = job.read_log(_text())
    assert log["devices"] == {"platform": "cpu", "device_kind": "cpu",
                              "device_count": 1, "mesh": {"data": 1}}
    assert [t["id"] for t in log["tasks"]] == list(range(1, 25))
    assert log["tasks"][0]["ms_per_step"] == 285.2 and log["tasks"][0]["steps"] == 8
    assert log["counts"]["finished_training"] == 24
    assert log["metrics_url"] == "http://127.0.0.1:49565/metrics"


def test_window_edges(job):
    log = job.read_log(_text())
    fig = job.window_figures(log, TRAFFIC, seconds=1.0)
    # opens at task 2's stamp (25.143), closes 1 s later: tasks 3..14 are in,
    # task 15 (26.173) is out
    assert fig["tasks"] == 12 and fig["steps"] == 96
    assert fig["samples"] == 96 * 256
    assert fig["wall_s"] == pytest.approx(26.112 - 25.143, abs=1e-6)
    inside = log["tasks"][2:14]
    # one reading per task, its records over the time since the completion
    # before it; the window's rate is the median reading
    stamps = [log["tasks"][1]["at"]] + [t["at"] for t in inside]
    rates = [8 * 256 / (b - a) for a, b in zip(stamps, stamps[1:])]
    assert fig["samples_per_s"] == pytest.approx(statistics.median(rates))
    assert min(rates) < fig["samples_per_s"] < max(rates)
    in_steps = sum(8 * t["ms_per_step"] / 1e3 for t in inside)
    # the driver's own figure: no per-layer metric reads it since PR 66 (the
    # `gap_*` metrics time the worker loop from the program's spans)
    assert fig["host_wait_pct"] == pytest.approx(100 * (1 - in_steps / fig["wall_s"]))
    assert fig["step_ms"] == statistics.median(t["ms_per_step"] for t in inside)
    # per-layer figures only from tasks that began after a given stamp
    later = job.window_figures(log, TRAFFIC, seconds=1.0, after=log["tasks"][7]["at"])
    assert later["tasks"] == 12 and later["wall_s"] == fig["wall_s"]
    assert later["step_ms"] == statistics.median(t["ms_per_step"] for t in log["tasks"][8:14])
    assert job.window_figures(log, dict(TRAFFIC, warmup_tasks=24), 1.0) == {}


def test_accounting_of_a_clean_run(job):
    log = job.read_log(_text())
    stopped = common.stamp("[2026-09-26 18:21:27,500]")
    # the preemption's requeue and the launcher's traceback come after the stop
    assert job.accounting_ok(log, stopped) == []


def test_a_re_leased_task_counts_as_failed(job):
    lines = _text().splitlines()
    again = [
        "[master] [2026-09-26 18:21:25,600] [WARNING] [elasticdl_tpu.master.task_dispatcher:718] task 7 lease expired (worker 0); requeued",
        lines[18].replace("18:21:25,590", "18:21:25,640"),      # task 7 completes again
    ]
    assert "training task 7:" in again[1]
    log = job.read_log("\n".join(lines[:20] + again + lines[20:]))
    problems = job.accounting_ok(log, common.stamp("[2026-09-26 18:21:27,500]"))
    assert any("lease expired" in p for p in problems)
    assert any("completed twice" in p for p in problems)


def test_a_failed_count_and_a_missing_accounting(job):
    text = _text()
    log = job.read_log(text.replace("'failed_permanently': 0", "'failed_permanently': 1"))
    assert any("failed" in p for p in job.accounting_ok(log, 2e9))
    log = job.read_log("\n".join(l for l in text.splitlines() if "job finished" not in l))
    assert any("no `job finished`" in p for p in job.accounting_ok(log, 2e9))
