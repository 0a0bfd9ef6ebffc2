"""Compiled-step HLO audit (ISSUE 7): utils/hlo.py parsers, the
tools/hlo_audit.py CLI gate, and the telemetry-header stamping.

The contract: donation coverage / dot dtype / collective counts are
readable from the program text, the lint gate exits nonzero exactly
when a large param/opt-state plane is undonated, and every
telemetry-carrying run's header carries the lowering audit for free.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.utils import hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_step(donate=True):
    def f(p, o, x):
        g = (x.astype(jnp.bfloat16) @ p.astype(jnp.bfloat16)) \
            .astype(jnp.float32).sum(0)
        return p - 0.1 * g, o * 0.9, g.sum()

    jf = jax.jit(f, donate_argnums=(0, 1) if donate else ())
    p = jnp.ones((64, 64))
    o = jnp.ones((64, 64))
    x = jnp.ones((8, 64))
    return jf, (p, o, x)


class TestHloParsers:
    def test_lowering_summary_donation_and_dtypes(self):
        jf, args = _toy_step()
        s = hlo.lowering_summary(jf.lower(*args), args,
                                 arg_labels=("p", "o", "x"))
        assert s["source"] == "lowering"
        assert s["donation"]["p"]["donated_leaves"] == 1
        assert s["donation"]["o"]["donated_leaves"] == 1
        assert s["donation"]["x"]["donated_leaves"] == 0
        assert not s["donation"]["p"]["undonated"]
        # the program requests a bf16 matmul; the lowering says so even
        # on CPU (the backend's own f32 rewrite is a different layer)
        assert s["dot_conv_dtypes"]["dot"] == {"bf16": 1}

    def test_lowering_summary_flags_undonated(self):
        jf, args = _toy_step(donate=False)
        s = hlo.lowering_summary(jf.lower(*args), args,
                                 arg_labels=("p", "o", "x"))
        assert s["donation"]["p"]["donated_leaves"] == 0
        assert [u["path"] for u in s["donation"]["p"]["undonated"]] == ["p"]
        bad = hlo.undonated_planes(s, expected=("p", "o"))
        assert [label for label, _ in bad] == ["p", "o"]

    def test_compiled_summary_alias_table(self):
        jf, args = _toy_step()
        s = hlo.compiled_summary(jf.lower(*args).compile(), args,
                                 arg_labels=("p", "o", "x"))
        assert s["source"] == "compiled"
        assert s["donation"]["p"]["donated_leaves"] == 1
        assert s["donation"]["o"]["donated_leaves"] == 1
        assert s["fusions"] >= 0
        assert not hlo.undonated_planes(s, expected=("p", "o"))

    def test_min_bytes_spares_scalars(self):
        def f(p, n):
            return p * 2.0, n + 1

        jf = jax.jit(f)               # nothing donated
        p = jnp.ones((64, 64))
        n = jnp.zeros((), jnp.float32)
        s = hlo.audit_step(jf, p, n, arg_labels=("p", "n"), compile=False)
        # the large plane is flagged, the scalar is not a leak
        assert s["donation"]["p"]["undonated"]
        assert not s["donation"]["n"]["undonated"]

    def test_collectives_counted_under_shard_map(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        if len(jax.devices()) < 2:
            pytest.skip("psum over a 1-device axis is elided at lowering")
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

        def body(x):
            return jax.lax.psum(x.sum(), "data")

        jf = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"),),
                               out_specs=P(), check_vma=False))
        x = jnp.ones((4, 8))
        s = hlo.lowering_summary(jf.lower(x), (x,), arg_labels=("x",))
        assert s["collectives"].get("all_reduce", 0) >= 1


class TestHloAuditCLI:
    """ISSUE-7 satellite: fast tier-1 smoke for the local driver's step
    -- params/opt-state donated, strict-JSON output, and the gate
    actually trips when donation is dropped."""

    def _run(self, *argv):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        return subprocess.run(
            [sys.executable, "-m", "tools.hlo_audit", *argv],
            capture_output=True, text=True, cwd=REPO, env=env,
            timeout=300)

    def test_local_driver_smoke(self):
        proc = self._run("--driver", "local")
        assert proc.returncode == 0, proc.stderr[-800:]

        def _no_nan(x):
            raise AssertionError(f"non-strict JSON constant: {x}")

        rep = json.loads(proc.stdout, parse_constant=_no_nan)
        local = rep["drivers"]["local"]
        assert local["source"] == "compiled"
        d = local["donation"]
        assert d["params"]["donated_leaves"] == d["params"]["leaves"]
        assert d["opt_state"]["donated_leaves"] == d["opt_state"]["leaves"]
        assert local["gate"]["ok"] and rep["gate"]["ok"]
        assert "dot" in local["dot_conv_dtypes"]

    def test_gate_exits_nonzero_on_undonated_plane(self, capsys):
        """In-process (no second jax import): main() returns nonzero and
        names the undonated planes when the local step drops donation."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "_t_hlo_audit", os.path.join(REPO, "tools", "hlo_audit.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        rc = mod.main(["--driver", "local", "--no-donate"])
        assert rc != 0
        rep = json.loads(capsys.readouterr().out)
        planes = [p["plane"] for p in
                  rep["drivers"]["local"]["gate"]["undonated_planes"]]
        assert "params" in planes and "opt_state" in planes
        assert rep["gate"]["failed"] == ["local"]

    def test_gate_list_validated(self, capsys):
        """A typo'd / space-padded --gate entry must not silently ungate
        a driver: unknown names are an argparse error (exit 2)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "_t_hlo_audit2", os.path.join(REPO, "tools", "hlo_audit.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        with pytest.raises(SystemExit) as e:
            mod.main(["--driver", "local", "--gate", "lcoal"])
        assert e.value.code == 2
        capsys.readouterr()

    @pytest.mark.slow
    def test_all_drivers_pass_gate(self):
        """Acceptance: donation/dtype/collective summaries for all three
        drivers' steps; local + distri (and tp, after the out_shardings
        pin) pass the donation gate."""
        proc = self._run()
        assert proc.returncode == 0, proc.stdout[-800:] + proc.stderr[-400:]
        rep = json.loads(proc.stdout)
        assert set(rep["drivers"]) == {"local", "distri", "tp"}
        for name, s in rep["drivers"].items():
            assert s["gate"]["ok"], (name, s["gate"])
        assert rep["drivers"]["distri"]["collectives"]
        assert rep["drivers"]["tp"]["fusions"] > 0


class TestHeaderStamping:
    def test_local_run_header_carries_compiled_step(self):
        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim
        from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
        from bigdl_tpu.observability import StepTelemetry

        rng = np.random.default_rng(0)
        x = rng.standard_normal((48, 16)).astype("float32")
        y = rng.integers(0, 4, 48).astype("int32")
        ds = array_dataset(x, y) >> SampleToMiniBatch(16)
        m = (nn.Sequential().add(nn.Linear(16, 32)).add(nn.ReLU())
             .add(nn.Linear(32, 4)))
        with tempfile.TemporaryDirectory() as td:
            tel = StepTelemetry(td, trace=False)
            opt = optim.LocalOptimizer(m, ds, nn.CrossEntropyCriterion(),
                                       optim.SGD(learning_rate=0.05))
            opt.set_end_when(optim.Trigger.max_iteration(2))
            opt.set_telemetry(tel)
            opt.optimize()
            tel.close()
            with open(os.path.join(td, "telemetry.jsonl")) as f:
                header = json.loads(f.readline())
            cs = header["compiled_step"]
            assert cs["source"] == "lowering"
            cov = cs["donation"]
            assert cov["params"]["donated_leaves"] == cov["params"]["leaves"]
            assert cov["opt_state"]["donated_leaves"] \
                == cov["opt_state"]["leaves"]
            assert cov["input"]["donated_leaves"] == 0
            # the obs_report section renders from the same header
            sys.path.insert(0, os.path.join(REPO, "tools"))
            try:
                import importlib.util
                spec = importlib.util.spec_from_file_location(
                    "_t_obs", os.path.join(REPO, "tools", "obs_report.py"))
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
            finally:
                sys.path.pop(0)
            rep = mod.build_report(td)
            assert rep["compiled_step"] == cs
            text = mod.format_report(rep)
            assert "compiled step (lowering audit):" in text
