"""tools/perf_gate.py (ISSUE 9): the trusted-only BENCH trajectory and
its regression gate, plus the obs_report satellites (supervised-run
artifact roots merge into one report; a hollow run dir exits nonzero).
No jax import in either tool -- both are spec-loaded by file path."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def gate():
    return _load("_t_perf_gate", "tools", "perf_gate.py")


@pytest.fixture(scope="module")
def obs():
    return _load("_t_obs_gate", "tools", "obs_report.py")


def _trusted_record(value, metric="m_imgs_per_sec", **extra_fields):
    extra = {"platform": "tpu", "sec_per_step_blocked": 0.1,
             "steps": 20, **extra_fields}
    return {"metric": metric, "value": value, "unit": "images/sec",
            "vs_baseline": 1.0, "trust": "trusted", "extra": extra}


def _wrapper(records, n=1, rc=0, superseded=False):
    doc = {"n": n, "cmd": "python bench.py", "rc": rc,
           "tail": "\n".join(json.dumps(r) for r in records),
           "parsed": records[-1] if records else None}
    if superseded:
        doc["superseded"] = True
    return doc


def _bench_dir(tmp_path, files):
    d = tmp_path / "bench"
    d.mkdir()
    for name, doc in files.items():
        (d / name).write_text(json.dumps(doc))
    return str(d)


class TestTrajectory:
    def test_checked_in_history_builds_and_passes(self, gate, tmp_path,
                                                  capsys):
        """The REAL repo artifacts: r02 (superseded async artifact) is
        excluded, r02_judge is the one trusted baseline -- and the gate
        passes.  A CPU-fallback record of the shape bench.py emits when
        no chip answers (platform "cpu" under the device metric's name)
        is invalid:off_tpu and neither sets nor breaks a baseline."""
        rc = gate.main(["--dir", REPO])
        out = capsys.readouterr().out
        assert rc == 0
        assert "gate: PASS" in out
        assert "r02_judge" in out and "trusted" in out
        assert "SUPERSEDED" in out

        cpu_fallback = {
            "metric": "resnet50_train_imgs_per_sec_per_chip", "value": 0.6,
            "unit": "images/sec", "vs_baseline": 0.0,
            "extra": {"platform": "cpu", "device_kind": "cpu", "batch": 8,
                      "steps": 2, "sec_per_step": 13.4346,
                      "cpu_fallback": True}}
        d = _bench_dir(tmp_path, {
            "BENCH_r04.json": _wrapper([cpu_fallback], n=4)})
        assert gate.main(["--dir", d]) == 0
        assert "invalid:off_tpu" in capsys.readouterr().out

    def test_round_ordering_and_judge_subrank(self, gate):
        assert gate._round_key("/x/BENCH_r02.json") \
            < gate._round_key("/x/BENCH_r02_judge.json") \
            < gate._round_key("/x/BENCH_r03.json")

    def test_wrapper_parsing_drops_incomplete_diagnostics(self, gate):
        records = [
            {"metric": "m", "value": 0.0,
             "extra": {"error": "incomplete: killed during probe"}},
            {"metric": "m", "value": 5.0, "extra": {}},
        ]
        recs = gate._record_lines("\n".join(json.dumps(r)
                                            for r in records))
        assert [r["value"] for r in recs] == [5.0]

    def test_ratio_records_are_baseline_eligible(self, gate):
        # host-side A/B ratios carry no platform/timing claim: the
        # device trust verdicts do not apply, the ratio still gates
        rec = {"metric": "serving_coalesced_rps_speedup", "value": 4.0,
               "unit": "x", "extra": {"concurrency": 8}}
        assert gate.classify_trust(rec) == "ratio"
        # a CPU fallback that DID claim a platform stays excluded
        cpu = {"metric": "m", "value": 1.0,
               "extra": {"platform": "cpu", "sec_per_step": 0.5}}
        assert gate.classify_trust(cpu) == "invalid:off_tpu"

    def test_own_trust_verdict_is_kept(self, gate):
        rec = _trusted_record(10.0)
        rec["trust"] = "suspect:async_dispatch"
        assert gate.classify_trust(rec) == "suspect:async_dispatch"


class TestGate:
    def test_regression_fails(self, gate, tmp_path, capsys):
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([_trusted_record(1000.0)], n=1),
            "BENCH_r02.json": _wrapper([_trusted_record(500.0)], n=2),
        })
        rc = gate.main(["--dir", d])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION" in out and "gate: FAIL" in out

    def test_improvement_and_tolerance_pass(self, gate, tmp_path):
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([_trusted_record(1000.0)], n=1),
            "BENCH_r02.json": _wrapper([_trusted_record(980.0)], n=2),
        })
        assert gate.main(["--dir", d, "--tolerance", "0.05"]) == 0
        assert gate.main(["--dir", d, "--tolerance", "0.01"]) == 1

    def test_untrusted_record_cannot_set_or_break_baseline(self, gate,
                                                           tmp_path):
        cpu = _trusted_record(50000.0)
        cpu["trust"] = "invalid:off_tpu"
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([_trusted_record(1000.0)], n=1),
            # an absurd untrusted value neither raises the bar ...
            "BENCH_r02.json": _wrapper([cpu], n=2),
            "BENCH_r03.json": _wrapper([_trusted_record(990.0)], n=3),
        })
        assert gate.main(["--dir", d]) == 0

    def test_superseded_record_excluded(self, gate, tmp_path):
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([_trusted_record(9000.0)], n=1,
                                       superseded=True),
            "BENCH_r02.json": _wrapper([_trusted_record(1000.0)], n=2),
        })
        # 1000 vs the superseded 9000 is NOT a regression: the 9000 was
        # disavowed (exactly the r02 async-dispatch story)
        assert gate.main(["--dir", d]) == 0

    def test_check_candidate_against_history(self, gate, tmp_path,
                                             capsys):
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([_trusted_record(1000.0)], n=1),
        })
        cand = tmp_path / "BENCH_new.json"
        cand.write_text(json.dumps(_trusted_record(500.0)))
        rc = gate.main(["--dir", d, "--check", str(cand)])
        assert rc == 1
        assert "candidate" in capsys.readouterr().out
        cand.write_text(json.dumps(_trusted_record(1500.0)))
        assert gate.main(["--dir", d, "--check", str(cand)]) == 0

    def test_require_trusted_candidate(self, gate, tmp_path):
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([_trusted_record(1000.0)], n=1),
        })
        cand = tmp_path / "BENCH_new.json"
        cpu = _trusted_record(2000.0)
        cpu["trust"] = "invalid:off_tpu"
        cand.write_text(json.dumps(cpu))
        assert gate.main(["--dir", d, "--check", str(cand)]) == 0
        assert gate.main(["--dir", d, "--check", str(cand),
                          "--require-trusted"]) == 1

    def test_peak_bytes_metric_gates_lower_is_better(self, gate,
                                                     tmp_path, capsys):
        """ISSUE-18 satellite: ``*_bytes``/``*_peak`` records class as
        lower-is-better -- a synthetic regressed candidate (2x the
        baseline's peak bytes) must trip the gate, and a within-
        tolerance one must hold."""
        rec = _trusted_record(1_000_000.0, metric="serving_kv_peak_bytes")
        rec["unit"] = "bytes"
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([rec], n=1),
        })
        bad = dict(rec, value=2_000_000.0)
        cand = tmp_path / "BENCH_new.json"
        cand.write_text(json.dumps(bad))
        assert gate.main(["--dir", d, "--check", str(cand)]) == 1
        out = capsys.readouterr().out
        assert "lower-is-better" in out and "REGRESSION" in out
        cand.write_text(json.dumps(dict(rec, value=1_020_000.0)))
        assert gate.main(["--dir", d, "--check", str(cand)]) == 0

    def test_direction_classing(self, gate):
        """Explicit direction wins; ratio/saved names stay higher even
        when byte-flavored (``serving_paged_kv_bytes_ratio`` must not
        invert); peak/bytes suffixes go lower."""
        assert gate.metric_direction("serving_kv_peak_bytes") == "lower"
        assert gate.metric_direction("decode_peak") == "lower"
        assert gate.metric_direction(
            "serving_paged_kv_bytes_ratio") == "higher"
        assert gate.metric_direction(
            "serving_prefix_prefill_saved") == "higher"
        assert gate.metric_direction("m_imgs_per_sec") == "higher"
        assert gate.metric_direction(
            "whatever", {"direction": "lower"}) == "lower"

    def test_json_format_is_machine_readable(self, gate, tmp_path,
                                             capsys):
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": _wrapper([_trusted_record(1000.0)], n=1),
            "BENCH_r02.json": _wrapper([_trusted_record(400.0)], n=2),
        })
        rc = gate.main(["--dir", d, "--format", "json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1 and doc["ok"] is False
        assert doc["regressions"]
        entries = doc["trajectory"]["metrics"]["m_imgs_per_sec"]
        assert [e["value"] for e in entries] == [1000.0, 400.0]

    def test_empty_round_is_visible_evidence(self, gate, tmp_path,
                                             capsys):
        d = _bench_dir(tmp_path, {
            "BENCH_r01.json": {"n": 1, "cmd": "x", "rc": 124, "tail": "",
                               "parsed": None},
        })
        assert gate.main(["--dir", d]) == 0
        out = capsys.readouterr().out
        assert "no record (rc=124)" in out
        assert "NO baseline-eligible record" in out


# --------------------------------------------------------------------------- #
# obs_report satellites.
# --------------------------------------------------------------------------- #


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


def _serve_record(value, metric="serving_int8_rps_ratio"):
    """The BENCH_SERVE_INT8 A/B shape: a host-side ratio -- no platform
    claim, no per-step timing claim -- so the timing verdicts do not
    apply and the gate classes it ``ratio``."""
    return {"metric": metric, "value": value, "unit": "x",
            "vs_baseline": value,
            "extra": {"concurrency": 8, "requests": 400,
                      "fp32": {"requests_per_s": 9000.0, "p99_ms": 1.5,
                               "recompiles_after_precompile": 0},
                      "int8": {"requests_per_s": 9000.0 * value,
                               "p99_ms": 1.7,
                               "recompiles_after_precompile": 0,
                               "accuracy_gate": {"ok": True}}}}


class TestServeInt8Records:
    """ISSUE-11 satellite: the BENCH_SERVE int8 A/B's req/s metric rides
    the trusted trajectory as a ``ratio`` record, so an int8 serving
    regression trips the gate exactly like an MFU regression."""

    def test_serve_ab_classes_as_ratio_and_sets_baseline(self, gate,
                                                         tmp_path):
        assert gate.classify_trust(_serve_record(1.0)) == "ratio"
        d = _bench_dir(tmp_path, {
            "BENCH_r06.json": _wrapper([_serve_record(1.01)], n=6),
        })
        traj = gate.build_trajectory(d)
        entries = traj["metrics"]["serving_int8_rps_ratio"]
        assert entries[0]["trust"] == "ratio"
        assert entries[0]["baseline_eligible"] is True
        assert gate.main(["--dir", d]) == 0

    def test_int8_rps_regression_trips_the_gate(self, gate, tmp_path,
                                                capsys):
        d = _bench_dir(tmp_path, {
            "BENCH_r06.json": _wrapper([_serve_record(1.0)], n=6),
            "BENCH_r07.json": _wrapper([_serve_record(0.6)], n=7),
        })
        rc = gate.main(["--dir", d])
        out = capsys.readouterr().out
        assert rc == 1
        assert "serving_int8_rps_ratio" in out and "gate: FAIL" in out
        # and a --check candidate regressing the serve baseline fails too
        (tmp_path / "h2").mkdir()
        d2 = _bench_dir(tmp_path / "h2", {
            "BENCH_r06.json": _wrapper([_serve_record(1.0)], n=6)})
        cand = tmp_path / "BENCH_cand.json"
        cand.write_text(json.dumps(_serve_record(0.5)))
        assert gate.main(["--dir", d2, "--check", str(cand)]) == 1
        cand.write_text(json.dumps(_serve_record(0.99)))
        assert gate.main(["--dir", d2, "--check", str(cand)]) == 0

    def test_checked_in_r06_is_baseline_eligible(self, gate):
        """The REAL checked-in BENCH_r06.json: both int8 A/B metrics
        enter the trajectory as baseline-eligible ratio records, and
        gating it as a fresh candidate (the CI spelling from the
        acceptance criteria) passes."""
        path = os.path.join(REPO, "BENCH_r06.json")
        assert os.path.exists(path), "BENCH_r06.json must be checked in"
        records, note = gate.load_bench_file(path)
        assert note is None
        metrics = {r["metric"] for r in records}
        assert {"serving_int8_rps_ratio",
                "serving_int8_model_bytes_ratio"} <= metrics
        for r in records:
            assert gate.classify_trust(r) == "ratio"
        traj = gate.build_trajectory(REPO)
        for m in ("serving_int8_rps_ratio",
                  "serving_int8_model_bytes_ratio"):
            assert any(e["baseline_eligible"]
                       for e in traj["metrics"][m]), m
        assert gate.main(["--dir", REPO, "--check", path,
                          "--require-trusted"]) == 0


def _decode_record(value):
    """The BENCH_DECODE A/B shape: cached-over-uncached tokens/sec --
    a host-side ratio (no platform / per-step timing claim), so the
    gate classes it ``ratio`` and it rides the trusted trajectory."""
    return {"metric": "serving_decode_tokens_ratio", "value": value,
            "unit": "x", "vs_baseline": value / 3.0,
            "extra": {"prompt_len": 512, "new_tokens": 128,
                      "uncached": {"tokens_per_s": 12.0},
                      "cached": {"tokens_per_s": 12.0 * value,
                                 "recompiles_after_warm": 0},
                      "greedy_tokens_match": True}}


class TestDecodeRecords:
    """ISSUE-15 satellite: the BENCH_DECODE KV-cache A/B's tokens/sec
    metric is baseline-eligible ``ratio``, a synthetic regression trips
    rc 1, and the checked-in BENCH_r07.json passes the CI spelling."""

    def test_decode_ratio_classes_and_sets_baseline(self, gate, tmp_path):
        assert gate.classify_trust(_decode_record(10.0)) == "ratio"
        d = _bench_dir(tmp_path, {
            "BENCH_r07.json": _wrapper([_decode_record(10.0)], n=7),
        })
        traj = gate.build_trajectory(d)
        entries = traj["metrics"]["serving_decode_tokens_ratio"]
        assert entries[0]["trust"] == "ratio"
        assert entries[0]["baseline_eligible"] is True
        assert gate.main(["--dir", d]) == 0

    def test_decode_regression_trips_the_gate(self, gate, tmp_path,
                                              capsys):
        d = _bench_dir(tmp_path, {
            "BENCH_r07.json": _wrapper([_decode_record(10.0)], n=7),
            "BENCH_r08.json": _wrapper([_decode_record(5.0)], n=8),
        })
        rc = gate.main(["--dir", d])
        out = capsys.readouterr().out
        assert rc == 1
        assert "serving_decode_tokens_ratio" in out and "gate: FAIL" in out
        # the CI spelling: a --check candidate regressing the baseline
        (tmp_path / "h2").mkdir()
        d2 = _bench_dir(tmp_path / "h2", {
            "BENCH_r07.json": _wrapper([_decode_record(10.0)], n=7)})
        cand = tmp_path / "BENCH_cand.json"
        cand.write_text(json.dumps(_decode_record(4.0)))
        assert gate.main(["--dir", d2, "--check", str(cand),
                          "--require-trusted"]) == 1
        cand.write_text(json.dumps(_decode_record(9.9)))
        assert gate.main(["--dir", d2, "--check", str(cand),
                          "--require-trusted"]) == 0

    def test_checked_in_r07_is_baseline_eligible(self, gate):
        """The REAL checked-in BENCH_r07.json: the decode ratio enters
        the trajectory baseline-eligible, clears the >= 3x acceptance
        bar, and gating it as a fresh candidate passes."""
        path = os.path.join(REPO, "BENCH_r07.json")
        assert os.path.exists(path), "BENCH_r07.json must be checked in"
        records, note = gate.load_bench_file(path)
        assert note is None
        recs = [r for r in records
                if r["metric"] == "serving_decode_tokens_ratio"]
        assert recs, "BENCH_r07.json must carry the decode ratio record"
        for r in recs:
            assert gate.classify_trust(r) == "ratio"
            assert r["value"] >= 3.0            # the ISSUE-15 target
            assert r["extra"]["greedy_tokens_match"] is True
            assert r["extra"]["cached"]["recompiles_after_warm"] == 0
        traj = gate.build_trajectory(REPO)
        assert any(e["baseline_eligible"] for e in
                   traj["metrics"]["serving_decode_tokens_ratio"])
        assert gate.main(["--dir", REPO, "--check", path,
                          "--require-trusted"]) == 0


def _paged_record(value):
    """The BENCH_PAGED layout A/B shape: contiguous-over-paged cache
    bytes -- exact counts, no platform/timing claim, so ``ratio``."""
    return {"metric": "serving_paged_kv_bytes_ratio", "value": value,
            "unit": "x", "vs_baseline": value / 2.0,
            "extra": {"block_size": 16, "kv_blocks": 72,
                      "contiguous": {"cache_bytes": 10485760,
                                     "recompiles_after_precompile": 0},
                      "paged": {"cache_bytes": int(10485760 / value),
                                "recompiles_after_precompile": 0,
                                "recompiles_after_sampled": 0},
                      "greedy_tokens_match": True}}


class TestPagedRecords:
    """ISSUE-17 satellite: the paged-KV byte ratio and the
    shared-prefix prefill-saved fraction are baseline-eligible
    ``ratio`` records, a synthetic byte-ratio regression trips rc 1,
    and the REAL checked-in BENCH_r08.json clears the acceptance
    floors."""

    def test_paged_ratio_classes_and_regression_trips(self, gate,
                                                      tmp_path, capsys):
        assert gate.classify_trust(_paged_record(4.0)) == "ratio"
        d = _bench_dir(tmp_path, {
            "BENCH_r08.json": _wrapper([_paged_record(4.0)], n=8),
            "BENCH_r09.json": _wrapper([_paged_record(1.5)], n=9),
        })
        rc = gate.main(["--dir", d])
        out = capsys.readouterr().out
        assert rc == 1
        assert "serving_paged_kv_bytes_ratio" in out \
            and "gate: FAIL" in out

    def test_checked_in_r08_clears_the_acceptance_floors(self, gate):
        """The REAL BENCH_r08.json: >= 2x cache-byte reduction, paged
        tokens/s within 10% of contiguous, identical greedy streams, 0
        recompiles after precompile (sampled stretch included), and >=
        half the shared-prefix prompt compute cache-absorbed."""
        path = os.path.join(REPO, "BENCH_r08.json")
        assert os.path.exists(path), "BENCH_r08.json must be checked in"
        records, note = gate.load_bench_file(path)
        assert note is None
        by_metric = {r["metric"]: r for r in records}
        paged = by_metric["serving_paged_kv_bytes_ratio"]
        assert gate.classify_trust(paged) == "ratio"
        assert paged["value"] >= 2.0          # the ISSUE-17 floor
        e = paged["extra"]
        assert e["greedy_tokens_match"] is True
        assert e["tokens_per_s_ratio"] >= 0.9
        assert e["contiguous"]["recompiles_after_precompile"] == 0
        assert e["paged"]["recompiles_after_precompile"] == 0
        assert e["paged"]["recompiles_after_sampled"] == 0
        saved = by_metric["serving_prefix_prefill_saved"]
        assert gate.classify_trust(saved) == "ratio"
        assert saved["value"] >= 0.5
        traj = gate.build_trajectory(REPO)
        for m in ("serving_paged_kv_bytes_ratio",
                  "serving_prefix_prefill_saved"):
            assert any(en["baseline_eligible"]
                       for en in traj["metrics"][m]), m
        assert gate.main(["--dir", REPO, "--check", path,
                          "--require-trusted"]) == 0


def _spec_record(metric, value, unit="x", **extra):
    """The BENCH_SPEC shapes (ISSUE 19): host-side byte counts and
    tokens-per-verify -- no platform / per-step timing claim, so the
    gate classes all three ``ratio``."""
    return {"metric": metric, "value": value, "unit": unit,
            "vs_baseline": 1.0,
            "extra": {"block_size": 16, "spec_k": 4,
                      "greedy_tokens_match": True, **extra}}


class TestSpecRecords:
    """ISSUE-19 satellite: the int8-KV byte records and the
    speculative tokens-per-verify ratio ride the trajectory as
    baseline-eligible ``ratio`` records; ``*_kv_peak_bytes`` gates
    lower-is-better (pool growth trips rc 1 exactly like an MFU drop);
    the REAL checked-in BENCH_r09.json clears the acceptance floors."""

    def test_directions_and_trust_classing(self, gate):
        assert gate.metric_direction(
            "serving_int8_kv_peak_bytes") == "lower"
        assert gate.metric_direction(
            "serving_int8_kv_bytes_ratio") == "higher"
        assert gate.metric_direction(
            "serving_spec_tokens_ratio") == "higher"
        for rec in (_spec_record("serving_int8_kv_bytes_ratio", 3.5),
                    _spec_record("serving_int8_kv_peak_bytes", 672768,
                                 unit="bytes"),
                    _spec_record("serving_spec_tokens_ratio", 4.8)):
            assert gate.classify_trust(rec) == "ratio"

    def test_kv_peak_bytes_growth_trips_the_gate(self, gate, tmp_path,
                                                 capsys):
        rec = _spec_record("serving_int8_kv_peak_bytes", 672768,
                           unit="bytes")
        d = _bench_dir(tmp_path, {
            "BENCH_r09.json": _wrapper([rec], n=9)})
        cand = tmp_path / "BENCH_cand.json"
        cand.write_text(json.dumps(dict(rec, value=2 * 672768)))
        rc = gate.main(["--dir", d, "--check", str(cand)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "lower-is-better" in out and "REGRESSION" in out
        # shrinking the pool is an improvement, not a regression
        cand.write_text(json.dumps(dict(rec, value=672768 // 2)))
        assert gate.main(["--dir", d, "--check", str(cand)]) == 0

    def test_spec_tokens_regression_trips_the_gate(self, gate,
                                                   tmp_path):
        d = _bench_dir(tmp_path, {
            "BENCH_r09.json": _wrapper(
                [_spec_record("serving_spec_tokens_ratio", 4.8)], n=9)})
        cand = tmp_path / "BENCH_cand.json"
        cand.write_text(json.dumps(
            _spec_record("serving_spec_tokens_ratio", 2.0)))
        assert gate.main(["--dir", d, "--check", str(cand),
                          "--require-trusted"]) == 1
        cand.write_text(json.dumps(
            _spec_record("serving_spec_tokens_ratio", 4.7)))
        assert gate.main(["--dir", d, "--check", str(cand),
                          "--require-trusted"]) == 0

    def test_checked_in_r09_clears_the_acceptance_floors(self, gate):
        """The REAL BENCH_r09.json: >= 3x KV byte reduction at head_dim
        32, the peak-bytes record citing the ledger's narrow count,
        >= 1.5 tokens per verify with a bit-identical greedy stream,
        and 0 recompiles on every leg (sampled stretch included)."""
        path = os.path.join(REPO, "BENCH_r09.json")
        assert os.path.exists(path), "BENCH_r09.json must be checked in"
        records, note = gate.load_bench_file(path)
        assert note is None
        by_metric = {r["metric"]: r for r in records}
        ratio = by_metric["serving_int8_kv_bytes_ratio"]
        assert gate.classify_trust(ratio) == "ratio"
        assert ratio["value"] >= 3.0          # the ISSUE-19 floor
        e = ratio["extra"]
        assert e["int8"]["kv_dtype"] == "int8"
        assert e["fp32"]["recompiles_after_precompile"] == 0
        assert e["int8"]["recompiles_after_precompile"] == 0
        peak = by_metric["serving_int8_kv_peak_bytes"]
        assert gate.metric_direction(peak["metric"], peak) == "lower"
        assert peak["value"] == e["int8"]["kv_bytes"]
        assert peak["value"] * 3 <= e["fp32"]["kv_bytes"]
        spec = by_metric["serving_spec_tokens_ratio"]
        assert gate.classify_trust(spec) == "ratio"
        assert spec["value"] >= 1.5
        assert spec["extra"]["greedy_tokens_match"] is True
        assert spec["extra"]["spec"]["recompiles_after_sampled"] == 0
        assert 0.0 <= spec["extra"]["speculative"][
            "acceptance_rate"] <= 1.0
        traj = gate.build_trajectory(REPO)
        for m in ("serving_int8_kv_bytes_ratio",
                  "serving_int8_kv_peak_bytes",
                  "serving_spec_tokens_ratio"):
            assert any(en["baseline_eligible"]
                       for en in traj["metrics"][m]), m
        assert gate.main(["--dir", REPO, "--check", path,
                          "--require-trusted"]) == 0


class TestTracedRecords:
    """ISSUE-16 satellite: a bench record measured with always-sample
    tracing enabled (BIGDL_TRACE_SAMPLE=1) carries the overhead of a
    span write per request -- the gate must refuse it as a --check
    candidate BEFORE trust classing, even when the record stamped its
    own 'trusted' verdict."""

    def _traced(self, value=10.0):
        rec = _serve_record(value)
        rec["extra"]["tracing"] = {"sample_rate": 1.0,
                                   "always_sample": True}
        return rec

    def test_always_sample_overrides_own_trust_stamp(self, gate):
        rec = self._traced()
        rec["trust"] = "trusted"                 # the stamp loses
        assert gate.classify_trust(rec) == "invalid:traced"
        # a head-sampled run is NOT refused: 1% tracing is the
        # production default the numbers should represent
        ok = _serve_record(10.0)
        ok["extra"]["tracing"] = {"sample_rate": 0.01,
                                  "always_sample": False}
        assert gate.classify_trust(ok) == "ratio"

    def test_traced_candidate_is_refused(self, gate, tmp_path, capsys):
        d = _bench_dir(tmp_path, {
            "BENCH_r06.json": _wrapper([_serve_record(1.0)], n=6)})
        cand = tmp_path / "BENCH_cand.json"
        cand.write_text(json.dumps(self._traced(2.0)))  # even an
        rc = gate.main(["--dir", d, "--check", str(cand)])  # improvement
        out = capsys.readouterr().out
        assert rc == 1
        assert "always-sample tracing" in out

    def test_traced_history_record_cannot_set_baseline(self, gate,
                                                       tmp_path):
        d = _bench_dir(tmp_path, {
            "BENCH_r06.json": _wrapper([self._traced(5.0)], n=6),
            "BENCH_r07.json": _wrapper([_serve_record(1.0)], n=7)})
        traj = gate.build_trajectory(d)
        entries = traj["metrics"]["serving_int8_rps_ratio"]
        assert entries[0]["trust"] == "invalid:traced"
        assert entries[0]["baseline_eligible"] is False
        regs, _notes = gate.gate(traj)          # the inflated traced
        assert not regs                         # round is NOT the bar


def _wire_record(metric, value, **extra):
    """The BENCH_WIRE shapes (ISSUE 20): closed-loop req/s A/B and
    staged-weight wire bytes -- host-side ratios with no platform /
    per-step timing claim, so the gate classes both ``ratio``."""
    return {"metric": metric, "value": value, "unit": "x",
            "vs_baseline": 1.0,
            "extra": {"concurrency": 10, "pool_size": 2,
                      "recompiles_after_precompile": 0,
                      "outputs_bit_identical": True, **extra}}


class TestWireRecords:
    """ISSUE-20 satellite: the fleet transport A/B records ride the
    trajectory as baseline-eligible ``ratio`` records (both
    higher-is-better -- ``fleet_wire_bytes_ratio`` is a reduction
    factor like the paged-KV one, not a peak-bytes gauge); a
    regressed candidate trips rc 1; the REAL checked-in BENCH_r10.json
    clears the acceptance floors."""

    def test_directions_and_trust_classing(self, gate):
        assert gate.metric_direction("fleet_wire_rps_ratio") == "higher"
        assert gate.metric_direction(
            "fleet_wire_bytes_ratio") == "higher"
        for rec in (_wire_record("fleet_wire_rps_ratio", 6.7),
                    _wire_record("fleet_wire_bytes_ratio", 3.8)):
            assert gate.classify_trust(rec) == "ratio"

    def test_wire_regression_trips_the_gate(self, gate, tmp_path):
        d = _bench_dir(tmp_path, {
            "BENCH_r10.json": _wrapper(
                [_wire_record("fleet_wire_rps_ratio", 6.7),
                 _wire_record("fleet_wire_bytes_ratio", 3.8)], n=10)})
        cand = tmp_path / "BENCH_cand.json"
        # a transport that lost its throughput edge (ratio collapsed
        # toward the pickle wire) must NOT slide through the gate
        cand.write_text(json.dumps(
            _wire_record("fleet_wire_rps_ratio", 1.1)))
        assert gate.main(["--dir", d, "--check", str(cand),
                          "--require-trusted"]) == 1
        # ... nor an int8 staging path that quietly stopped shrinking
        cand.write_text(json.dumps(
            _wire_record("fleet_wire_bytes_ratio", 1.2)))
        assert gate.main(["--dir", d, "--check", str(cand),
                          "--require-trusted"]) == 1
        # within-tolerance noise passes
        cand.write_text(json.dumps(
            _wire_record("fleet_wire_rps_ratio", 6.5)))
        assert gate.main(["--dir", d, "--check", str(cand),
                          "--require-trusted"]) == 0

    def test_checked_in_r10_clears_the_acceptance_floors(self, gate):
        """The REAL BENCH_r10.json: binary wire >= 1.3x pickle req/s
        at the same closed-loop load, int8 staged weights <= 0.35x the
        fp32 wire bytes, bit-identical outputs, zero recompiles and
        zero pickle fallbacks on the measured legs."""
        path = os.path.join(REPO, "BENCH_r10.json")
        assert os.path.exists(path), "BENCH_r10.json must be checked in"
        records, note = gate.load_bench_file(path)
        assert note is None
        by_metric = {r["metric"]: r for r in records}
        rps = by_metric["fleet_wire_rps_ratio"]
        assert gate.classify_trust(rps) == "ratio"
        assert rps["value"] >= 1.3            # the ISSUE-20 floor
        e = rps["extra"]
        assert e["recompiles_after_precompile"] == 0
        assert e["pickle_fallbacks"] == 0
        assert e["outputs_bit_identical"] is True
        assert e["binary"]["requests_per_s"] >= \
            1.3 * e["pickle"]["requests_per_s"]
        nbytes = by_metric["fleet_wire_bytes_ratio"]
        assert gate.classify_trust(nbytes) == "ratio"
        assert nbytes["value"] >= 1 / 0.35    # int8 <= 0.35x fp32
        assert nbytes["extra"]["stage_bytes_int8"] * 100 <= \
            35 * nbytes["extra"]["stage_bytes_fp32"]
        assert nbytes["extra"]["int8_max_abs_err"] < 0.01
        traj = gate.build_trajectory(REPO)
        for m in ("fleet_wire_rps_ratio", "fleet_wire_bytes_ratio"):
            assert any(en["baseline_eligible"]
                       for en in traj["metrics"][m]), m
        assert gate.main(["--dir", REPO, "--check", path,
                          "--require-trusted"]) == 0
