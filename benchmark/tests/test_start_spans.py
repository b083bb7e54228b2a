"""The start-up readers (benchmark/start_spans.py) on a recorded job log (a
CPU rehearsal of PR 50, cut after its second task) and on in-memory trace
records: the seven figures, the partition, and None where there is no
ledger."""

import os

import pytest

from benchmark import common, start_spans

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "start_spans_job.log")
JOB_METRICS = ("setup_state_s", "setup_compile_s", "setup_cache_misses",
               "start_process_s", "start_backend_s", "start_first_task_s",
               "start_named_pct")


def _text():
    with open(FIXTURE) as f:
        return f.read()


def test_a_line_a_process():
    launcher, worker = start_spans.ledgers_of_log(_text())
    assert (launcher["role"], worker["role"]) == ("master", "worker-0")
    assert set(launcher["spans"]) == {"start.launch", "start.master", "start.spawn"}
    assert "start.first_task" in worker["spans"]
    assert start_spans.first_completion(_text()) == common.stamp(
        "[2026-10-01 23:00:42,385]")


def test_the_job_s_seven_figures_and_their_partition():
    text = _text()
    f = start_spans.job_figures(
        start_spans.ledgers_of_log(text), start_spans.first_completion(text))
    assert set(f) == set(JOB_METRICS)
    assert f["setup_state_s"] == 0.7331 and f["setup_compile_s"] == 1.553
    assert f["setup_cache_misses"] == 0
    # launcher's start to its master serving (2.9707), the spawn to main()
    assert f["start_process_s"] == pytest.approx(2.9707 + 2.7109, abs=1e-3)
    assert f["start_backend_s"] == pytest.approx(0.3876, abs=1e-3)
    # the first task's own time, cut at the completion line's stamp
    assert f["start_first_task_s"] == pytest.approx(0.2084 - 0.0026, abs=1e-3)
    launched, done = 1790895633.8103, common.stamp("[2026-10-01 23:00:42,385]")
    parts = sum(f[k] for k in JOB_METRICS[:2] + JOB_METRICS[3:6])
    assert parts == pytest.approx(
        (done - launched) * f["start_named_pct"] / 100.0, rel=0.01)
    assert 99.0 < f["start_named_pct"] <= 100.0


def test_a_log_without_the_line_gives_none(tmp_path, monkeypatch):
    text = "\n".join(l for l in _text().splitlines()
                     if start_spans.LEDGER_LINE not in l)
    assert start_spans.ledgers_of_log(text) == []
    assert start_spans.job_figures([], start_spans.first_completion(text)) is None
    # a worker's line alone (a launcher from before the ledger) is not enough
    worker = start_spans.ledgers_of_log(_text())[1:]
    assert start_spans.job_figures(worker, 1790895642.385) is None
    # and through `read`, as a metric's reader asks: the kept job.log
    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "cell")
    (tmp_path / "cell" / "job.log").write_text(text)
    run = {"workload": "cell", "job": {}}
    assert start_spans.read(run, "setup_state_s") is None
    (tmp_path / "cell" / "job.log").write_text(_text())
    assert start_spans.read(run, "setup_state_s") is None      # read once
    assert start_spans.read({"workload": "cell", "job": {}},
                            "start_named_pct") > 99.0
    assert start_spans.read({"workload": "absent", "job": {}}, "setup_state_s") is None


def _span(name, start, seconds, span_id, parent_id=None, **attrs):
    return {"kind": "span", "name": name, "trace_id": "t", "span_id": span_id,
            "parent_id": parent_id, "role": "", "ts": float(start),
            "dur_ms": 1e3 * seconds, **attrs}


def test_a_resident_cell_reads_the_records_of_its_own_process(monkeypatch, capsys):
    from elasticdl_tpu.observability import tracing

    monkeypatch.setattr(start_spans, "window_opened_at", lambda run: 60.0)
    records = tracing.get_tracer().records
    kept = list(records)
    records.clear()
    records.extend([
        _span("start.state", 10.0, 4.0, "a", programs=3, cache_misses=3),
        _span("compile", 20.0, 6.0, "b", program="train_many", aot=False,
              programs=1, cache_misses=1, backend_s=5.0),
        _span("compile", 30.0, 12.5, "c", program="train_many", aot=True,
              programs=1, cache_hits=1, backend_s=1.0),
        _span("compile", 70.0, 3.0, "d", program="eval_step", aot=False),
    ])
    try:
        run = {"setup_s": 55.0}
        assert start_spans.read(run, "setup_state_s") == 4.0
        assert start_spans.read(run, "setup_compile_s") == 18.5
        assert start_spans.read(run, "setup_cache_misses") == 4
        assert start_spans.read(run, "start_named_pct") is None
        assert capsys.readouterr().out.count(start_spans.LEDGER_LINE) == 1
        records.clear()
        assert start_spans.read({"setup_s": 55.0}, "setup_state_s") is None
    finally:
        records.clear()
        records.extend(kept)


def test_a_program_without_the_fold_gives_none(monkeypatch):
    from elasticdl_tpu.observability import tracing

    monkeypatch.delattr(tracing, "startup_ledger")
    assert start_spans.read({"setup_s": 1.0}, "setup_compile_s") is None


def test_every_new_metric_has_its_reader_and_its_entry():
    bench = common.load_json("..", "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in JOB_METRICS:
        assert entries[name]["moves"] == "setup_s"
        assert callable(common.load_module("layer_metrics", name).read)
    assert all("workloads" not in entries[n] for n in JOB_METRICS[:3])
    assert all(entries[n]["workloads"] == ["deepfm-criteo.job"]
               for n in JOB_METRICS[3:])
