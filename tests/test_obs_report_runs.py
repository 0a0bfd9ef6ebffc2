"""``tools/obs_report.py`` over whole runs: a supervised run's artifact
root merges into one report, and a hollow run dir exits nonzero.  The
tool imports no jax and is spec-loaded by file path."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def obs():
    spec = importlib.util.spec_from_file_location(
        "_t_obs_runs", os.path.join(REPO, "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_jsonl(path, events):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def _step(step, loss, **kw):
    return {"kind": "step", "ts": 1.0, "step": step, "epoch": 1,
            "wall_s": 0.1, "data_wait_s": 0.01, "device_s": 0.09,
            "loss": loss, "records": 8, "records_per_s": 80.0,
            "sync_skew": 0, **kw}


class TestObsReportSupervisedRoot:
    def _root(self, tmp_path):
        root = str(tmp_path / "drill")
        header = {"kind": "header", "ts": 1.0, "run": "attempt_0",
                  "schema_version": 1, "platform": "cpu"}
        _write_jsonl(os.path.join(root, "attempt_0", "telemetry.jsonl"),
                     [header] + [_step(s, 2.0 - 0.1 * s)
                                 for s in range(1, 6)])
        _write_jsonl(os.path.join(root, "attempt_1", "telemetry.jsonl"),
                     [dict(header, run="attempt_1")]
                     + [_step(s, 1.7 - 0.1 * s) for s in range(4, 9)])
        _write_jsonl(
            os.path.join(root, "supervisor", "telemetry.jsonl"),
            [{"kind": "header", "ts": 1.0, "run": "supervisor"},
             {"kind": "recovery", "ts": 2.0, "restart": 1,
              "cause": "process_death", "error": "rc=-9", "at_step": 6,
              "snapshot": "ckpt/checkpoint.4.pkl", "snapshot_step": 4,
              "steps_replayed": 2, "backoff_s": 0.25}])
        return root

    def test_artifact_root_merges_attempts(self, obs, tmp_path):
        rep = obs.build_report(self._root(tmp_path))
        assert rep["n_steps"] == 10          # 5 + 5 across attempts
        assert [a["attempt"] for a in rep["attempts"]] == [0, 1]
        assert rep["attempts"][0]["last_step"] == 5
        assert rep["attempts"][1]["first_step"] == 4
        # the Recovery section reads the supervisor dir directly
        assert rep["recovery"]["restarts"] == 1
        assert rep["recovery"]["causes"] == {"process_death": 1}
        # the header comes from the first attempt (device provenance)
        assert rep["header"]["run"] == "attempt_0"
        text = obs.format_report(rep)
        assert "supervised run: 2 attempt(s)" in text
        assert "attempt 1: 5 steps" in text

    def test_attempt_annotation_on_steps(self, obs, tmp_path):
        _, steps, _, _ = obs.load_supervised_run(self._root(tmp_path))
        assert {e["attempt"] for e in steps} == {0, 1}

    def test_cli_on_artifact_root(self, obs, tmp_path, capsys):
        assert obs.main([self._root(tmp_path), "--format", "json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["recovery"]["restarts"] == 1


class TestObsReportHollowRuns:
    def test_zero_events_exits_nonzero(self, obs, tmp_path, capsys):
        run = tmp_path / "empty"
        run.mkdir()
        (run / "telemetry.jsonl").write_text("")
        assert obs.main([str(run)]) == 2
        err = capsys.readouterr().err
        assert "zero step events" in err

    def test_header_only_run_exits_nonzero(self, obs, tmp_path, capsys):
        run = tmp_path / "headeronly"
        _write_jsonl(str(run / "telemetry.jsonl"),
                     [{"kind": "header", "ts": 1.0, "run": "x"}])
        assert obs.main([str(run)]) == 2

    def test_missing_jsonl_exits_nonzero_with_message(self, obs,
                                                      tmp_path, capsys):
        run = tmp_path / "nothing"
        run.mkdir()
        assert obs.main([str(run)]) == 2
        assert "telemetry.jsonl" in capsys.readouterr().err

    def test_serving_only_run_still_reports(self, obs, tmp_path, capsys):
        run = tmp_path / "serveonly"
        _write_jsonl(str(run / "telemetry.jsonl"),
                     [{"kind": "header", "ts": 1.0, "run": "serve"},
                      {"kind": "inference", "ts": 2.0, "step": 1,
                       "wall_s": 0.01, "records": 4, "bucket": 4,
                       "batch_fill": 1.0, "queue_depth": 0,
                       "request_latency_s": [0.01] * 4}])
        assert obs.main([str(run)]) == 0
        assert "serving" in capsys.readouterr().out

    def test_slo_section_renders(self, obs, tmp_path, capsys):
        run = tmp_path / "slorun"
        _write_jsonl(
            str(run / "telemetry.jsonl"),
            [{"kind": "header", "ts": 1.0, "run": "serve"},
             {"kind": "slo", "ts": 2.0, "objective": "p99_latency",
              "breach": True, "policy": "warn",
              "slo": "request_latency_s<=0.25 at 99.9000%"},
             {"kind": "slo", "ts": 3.0, "objective": "p99_latency",
              "breach": False, "policy": "warn",
              "slo": "request_latency_s<=0.25 at 99.9000%"}])
        rep = obs.build_report(str(run))
        assert rep["slo"]["objectives"][0]["breaches"] == 1
        assert rep["slo"]["objectives"][0]["breached_at_end"] is False
        assert obs.main([str(run)]) == 0
        out = capsys.readouterr().out
        assert "SLO [p99_latency]" in out and "recovered" in out
