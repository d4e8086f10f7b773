"""repro.serving: demand derivation, traffic traces, capacity planning.

The contracts under test:

* every config in ``repro.configs`` yields a finite, positive demand
  vector (encoder-only and attention-free families included);
* derived MPKI is monotone (non-decreasing) in context length;
* registered LLM workloads are first-class: they round-trip through
  ``sweep_spec``/``solve_spec`` with ONE jit trace per grid, and the
  workload registry restores cleanly;
* traffic generators and the CSV loader round-trip;
* the capacity planner runs end-to-end on the event engine and returns
  a concrete, area-sorted verdict list with the DES feeding the p99.
"""

import math

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import coaxial, memsim, workloads
from repro.core.cpu_model import solve_trace_count
from repro.core.devices import (MEASURED_NAMES, register_measured_devices,
                                unregister_measured_devices)
from repro.serving import capacity, demand, traffic


class TestDemand:
    def test_every_config_finite_positive(self):
        for arch in ARCHS:
            d = demand.decode_demand(arch)
            vec = dict(read=d.read_bytes, weight=d.weight_bytes,
                       flops=d.flops_per_token, inst=d.inst_per_token,
                       mpki=d.mpki, ipc=d.ipc, exec_frac=d.exec_frac,
                       ws_mb=d.ws_mb, compute_s=d.compute_s,
                       memory_s=d.memory_s)
            for k, v in vec.items():
                assert math.isfinite(v) and v > 0, (arch, k, v)
            assert math.isfinite(d.wb) and d.wb >= 0, arch

    def test_mpki_monotone_in_context(self):
        for arch in ARCHS:
            ms = [demand.decode_demand(arch, context=c).mpki
                  for c in (1024, 4096, 16384, 65536)]
            assert all(b >= a - 1e-12 for a, b in zip(ms, ms[1:])), \
                (arch, ms)

    def test_attention_archs_strictly_monotone(self):
        ms = [demand.decode_demand("mistral-large-123b", context=c).mpki
              for c in (1024, 4096, 16384)]
        assert ms[0] < ms[1] < ms[2]

    def test_encoder_only_has_no_kv(self):
        d = demand.decode_demand("hubert-xlarge")
        assert d.state_read_bytes == 0.0
        assert d.read_bytes == d.weight_bytes > 0

    def test_recurrent_state_context_free(self):
        a = demand.decode_demand("rwkv6-1.6b", context=1024)
        b = demand.decode_demand("rwkv6-1.6b", context=65536)
        assert a.state_read_bytes == b.state_read_bytes > 0

    def test_batch_amortizes_weights_only(self):
        small = demand.decode_demand("stablelm-1.6b", batch=8)
        big = demand.decode_demand("stablelm-1.6b", batch=256)
        assert big.weight_bytes < small.weight_bytes
        assert big.state_read_bytes == small.state_read_bytes

    def test_streaming_anchor(self):
        # A KV-dominated small model must land in the STREAM-like corner
        # of Table 4's (ipc, exec_frac) plane -- the fit's anchor.
        d = demand.decode_demand("stablelm-1.6b", context=32768)
        assert d.ipc < 0.4
        assert d.exec_frac < 0.15

    def test_rejects_bad_operating_point(self):
        with pytest.raises(ValueError):
            demand.decode_demand("stablelm-1.6b", batch=0)


class TestWorkloadRegistry:
    def test_round_trip_through_solve_spec(self):
        with coaxial.scoped_registry():
            wls = demand.register_llm_workloads(("stablelm-1.6b",))
            w = workloads.by_name("llm-stablelm-1.6b")
            assert w is wls[0] and w.suite == demand.LLM_SUITE
            assert w in workloads.all_workloads()
            spec = coaxial.sweep_spec(design=coaxial.all_designs())
            before = solve_trace_count()
            sw = coaxial.solve_spec(spec,
                                    workloads=workloads.all_workloads())
            assert solve_trace_count() == before + 1    # one trace/grid
            assert "llm-stablelm-1.6b" in sw.names
            i = sw.names.index("llm-stablelm-1.6b")
            cmpn = sw.comparison(coaxial.COAXIAL_4X)
            assert math.isfinite(float(cmpn.speedup[i]))
            assert float(cmpn.speedup[i]) > 0
        assert all(not n.startswith("llm-")
                   for n in (w.name for w in workloads.all_workloads()))

    def test_register_is_idempotent_and_restores(self):
        with coaxial.scoped_registry():
            n0 = len(workloads.all_workloads())
            a = demand.register_llm_workloads(("rwkv6-1.6b",))
            b = demand.register_llm_workloads(("rwkv6-1.6b",))
            assert a == b and len(workloads.all_workloads()) == n0 + 1
            demand.unregister_llm_workloads(("rwkv6-1.6b",))
            assert len(workloads.all_workloads()) == n0

    def test_measured_devices_round_trip(self):
        base = {d.name for d in coaxial.all_designs()}
        assert not (base & set(MEASURED_NAMES))    # opt-in, not default
        with coaxial.scoped_registry():
            register_measured_devices()
            now = {d.name for d in coaxial.all_designs()}
            assert set(MEASURED_NAMES) <= now
        assert {d.name for d in coaxial.all_designs()} == base


class TestTraffic:
    def test_synthetic_diurnal_shape(self):
        t = traffic.synthetic_diurnal(n_epochs=6, peak_rps=2.0,
                                      trough_frac=0.25)
        assert len(t.epochs) == 6
        assert t.peak_rps <= 2.0
        assert min(e.rps for e in t.epochs) >= 0.25 * 2.0 * 0.99
        assert all(e.kappa >= 1.0 for e in t.epochs)

    def test_poisson_burst_seeded(self):
        a = traffic.poisson_burst(seed=7)
        b = traffic.poisson_burst(seed=7)
        c = traffic.poisson_burst(seed=8)
        assert a == b
        assert a != c

    def test_csv_round_trip(self, tmp_path):
        t = traffic.synthetic_diurnal(n_epochs=4)
        path = str(tmp_path / "diurnal.csv")
        t.to_csv(path)
        back = traffic.load_csv(path)
        assert len(back.epochs) == 4
        for e0, e1 in zip(t.epochs, back.epochs):
            assert e1.rps == pytest.approx(e0.rps, rel=1e-5)
            assert e1.kappa == pytest.approx(e0.kappa, rel=1e-5)
        assert traffic.get_trace(path).epochs == back.epochs

    def test_get_trace_names(self):
        assert traffic.get_trace("synthetic-diurnal").name == \
            "synthetic-diurnal"
        with pytest.raises(KeyError):
            traffic.get_trace("no-such-trace")

    @staticmethod
    def _write(tmp_path, body):
        p = tmp_path / "trace.csv"
        p.write_text(body)
        return str(p)

    def test_csv_rejects_nonmonotone_t(self, tmp_path):
        path = self._write(tmp_path,
                           "t_s,rps\n0,1.0\n120,1.5\n60,2.0\n")
        with pytest.raises(ValueError, match=r"trace\.csv:4.*precedes"):
            traffic.load_csv(path)

    def test_csv_rejects_duplicate_t(self, tmp_path):
        path = self._write(tmp_path, "t_s,rps\n0,1.0\n60,1.5\n60,2.0\n")
        with pytest.raises(ValueError,
                           match=r"trace\.csv:4.*duplicates"):
            traffic.load_csv(path)

    def test_csv_rejects_negative_rps(self, tmp_path):
        path = self._write(tmp_path, "t_s,rps\n0,1.0\n60,-0.5\n")
        with pytest.raises(ValueError,
                           match=r"trace\.csv:3.*negative rps"):
            traffic.load_csv(path)

    def test_csv_rejects_sub_floor_kappa(self, tmp_path):
        path = self._write(tmp_path, "0,1.0,1.2\n60,1.0,0.5\n")
        with pytest.raises(ValueError, match=r"trace\.csv:2.*floor"):
            traffic.load_csv(path)

    def test_csv_rejects_garbage_mid_file(self, tmp_path):
        # Only the FIRST row may be a non-numeric header; a later
        # unparseable row is an error with its line number, not a row
        # silently skipped.
        path = self._write(tmp_path, "t_s,rps\n0,1.0\nsixty,2.0\n")
        with pytest.raises(ValueError,
                           match=r"trace\.csv:3.*non-numeric t_s"):
            traffic.load_csv(path)
        path = self._write(tmp_path, "t_s,rps\n0,1.0\n60,fast\n")
        with pytest.raises(ValueError, match=r"trace\.csv:3"):
            traffic.load_csv(path)

    def test_csv_rejects_short_row(self, tmp_path):
        path = self._write(tmp_path, "t_s,rps\n0,1.0\n60\n")
        with pytest.raises(ValueError,
                           match=r"trace\.csv:3.*expected t_s"):
            traffic.load_csv(path)

    def test_csv_accepts_comments_and_header(self, tmp_path):
        path = self._write(tmp_path,
                           "# measured trace\nt_s,rps,kappa\n"
                           "0,1.0,1.3\n\n# gap comment\n60,2.0,1.8\n")
        t = traffic.load_csv(path)
        assert len(t.epochs) == 2
        assert t.epochs[1].rps == 2.0 and t.epochs[1].kappa == 1.8

    def test_scaled(self):
        t = traffic.synthetic_diurnal(peak_rps=1.0)
        assert t.scaled(3.0).peak_rps == pytest.approx(3.0 * t.peak_rps)


class TestCapacity:
    def test_plan_end_to_end(self):
        trace = traffic.synthetic_diurnal(n_epochs=2)
        before = memsim.sim_trace_count("event")
        plan = capacity.plan_capacity(
            ("stablelm-1.6b",), trace, slo_p99_ms=10_000.0,
            batch=32, context=2048, channels=(2, 4), premium_ns=(30.0,),
            tier_splits=(0.0, 0.5), include_registry=False,
            include_measured=True, peak_util=0.6, steps=8_000,
            engine="event")
        # ONE batched DES run fed every (variant, epoch, lane) cell (0
        # new traces if this flat cell count was already compiled).
        assert memsim.sim_trace_count("event") - before <= 1
        assert plan.best is not None          # generous SLO -> a pick
        areas = [v.rel_area for v in plan.verdicts]
        assert areas == sorted(areas)         # cheapest-first contract
        names = {v.name for v in plan.verdicts}
        assert "ddr-baseline" in names
        assert any(n.startswith("cxl-dev-") for n in names)
        assert any("+tier" in n for n in names)
        for v in plan.verdicts:
            assert v.token_p99_ms > 0 and math.isfinite(v.token_p99_ms)
            assert v.access_p99_ns > 0        # DES actually fed the p99
            assert 0.0 < v.peak_rho <= 0.95
        assert plan.best.rel_area == min(
            v.rel_area for v in plan.verdicts if v.meets_slo)

    def test_impossible_slo_has_closest(self):
        trace = traffic.synthetic_diurnal(n_epochs=1)
        plan = capacity.plan_capacity(
            ("stablelm-1.6b",), trace, slo_p99_ms=1e-6, batch=32,
            context=2048, channels=(2,), premium_ns=(30.0,),
            tier_splits=(0.0,), include_registry=False,
            include_measured=False, peak_util=0.5, steps=8_000,
            engine="event")
        assert plan.best is None
        assert plan.closest.token_p99_ms == min(
            v.token_p99_ms for v in plan.verdicts)

    def test_tiered_area_between_pure_points(self):
        # A 50/50 DDR+CXL tier pays more pins than pure-CXL but its area
        # sits near the pure points (cores+LLC dominate Table 1).
        designs = capacity.candidate_designs(
            channels=(4,), premium_ns=(30.0,), include_registry=False,
            include_measured=False)
        cxl4 = next(d for d in designs if d.name.startswith("cxl-4ch"))
        variants = capacity._variants([cxl4], (0.0, 0.5))
        pure = next(v for v in variants if v.tier_split == 0.0)
        tier = next(v for v in variants if v.tier_split == 0.5)
        assert tier.rel_pins > pure.rel_pins
        assert tier.n_hot == 2 and tier.n_cold == 2
        assert abs(tier.rel_area - pure.rel_area) < 0.25

    def test_capacity_scales_with_channels(self):
        designs = {d.name: d for d in capacity.candidate_designs(
            channels=(2, 8), premium_ns=(30.0,), include_registry=False,
            include_measured=False)}
        c2 = capacity.capacity_gbps(designs["cxl-2ch-llc1-30ns"])
        c8 = capacity.capacity_gbps(designs["cxl-8ch-llc1-30ns"])
        assert c8 == pytest.approx(4.0 * c2)


class TestCLI:
    def test_plan_cli_smoke(self, capsys):
        from repro.serving import plan as plan_cli
        rc = plan_cli.main([
            "--arch", "stablelm-1.6b", "--slo-p99-ms", "10000",
            "--trace", "synthetic-diurnal", "--batch", "32",
            "--context", "2048", "--channels", "2", "4",
            "--premium-ns", "30", "--tier-splits", "0",
            "--no-measured", "--steps", "8000"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "PICK " in out and "channels=" in out
