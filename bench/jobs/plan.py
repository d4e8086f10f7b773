"""Job kind ``plan``: one capacity plan, ``serving.capacity.plan_capacity``
at the configuration's serving point, its DES p99 path on the event
engine.

The answer is the plan as the user gets it (every variant's name, area,
pins, IPC, access p99, token p99 and mean, SLO verdict, in the plan's
order, and the pick) and the planner's one batched DES call: each lane's
operating point (utilisation, burstiness, population bound, transfer
time, CXL premium) and the mean and p99 latency the DES returned.

The check builds the same plan with the plain references
(``bench/reference/planner.py`` for everything around the DES,
``bench/reference/des.py`` for the DES) from the configuration alone.
It compares every job's lane operating points, IPCs, areas and pins;
the DES statistics of a sample of (job, lane) pairs drawn from the run's
seed, simulated at the reference's operating points; and, for whole jobs
drawn from the seed, all their lanes and the plan composed from them:
every variant's access p99, token p99 and mean, verdict, and the pick.
"""

from __future__ import annotations

import numpy as np

from bench.harness.runner import Check, sample_rng
from bench.reference import des, planner

#: Lane fields the planner sets; every other channel field comes from the
#: configuration.
PLANNER_FIELDS = ("rho", "kappa", "outstanding", "t_xfer_ns", "cxl_lat_ns")
#: Fields of each variant's verdict that the check reads, besides its IPC
#: (one arch, so the first of the program's per-arch tuple).
VERDICT_FIELDS = ("name", "rel_area", "rel_pins", "access_p99_ns",
                  "token_p99_ms", "token_mean_ms", "meets_slo")


def _rel(got, ref) -> float:
    """Widest gap of ``got`` from ``ref``, relative to ``ref`` (a gap
    from an exact 0 counts in full)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)
                        / np.maximum(np.abs(ref), 1e-30), initial=0.0))


class Plan:
    def __init__(self, cell):
        c = self.config = cell.config
        self.model, self.serving = dict(c["model"]), dict(c["serving"])
        self.channel = dict(c["channel"])
        self.steps, self.engine = int(c["steps"]), c["engine"]
        args = cell.traffic["args"]
        self.devices = int(args["devices"])
        self.check_lanes = int(args["check_lanes"])
        self.check_jobs = int(args["check_jobs"])

    def setup(self, probe) -> None:
        from repro.configs import get_config
        from repro.serving.traffic import get_trace
        cfg = get_config(self.model["arch"])
        have = dict(num_hidden_layers=cfg.n_layers,
                    hidden_size=cfg.d_model,
                    num_attention_heads=cfg.n_heads,
                    num_key_value_heads=cfg.n_kv_heads,
                    intermediate_size=cfg.d_ff, vocab_size=cfg.vocab)
        differ = {k: (v, self.model[k]) for k, v in have.items()
                  if self.model[k] != v}
        if differ:
            raise ValueError(f"the program's {self.model['arch']} differs "
                             f"from the configuration: {differ}")
        self.trace = get_trace(self.serving["trace"])
        self.probe = probe

    def run(self, seed: int) -> dict:
        from repro.serving.capacity import plan_capacity
        s = self.serving
        n0 = len(self.probe.calls)
        plan = plan_capacity(
            (self.model["arch"],), self.trace,
            slo_p99_ms=s["slo_p99_ms"], batch=s["batch"],
            context=s["context"], tokens_per_req=s["tokens_per_req"],
            channels=tuple(s["channels"]), llc_mb=tuple(s["llc_mb"]),
            premium_ns=tuple(s["premium_ns"]),
            tier_splits=tuple(s["tier_splits"]),
            include_measured=s["include_measured"],
            peak_util=s["peak_util"], steps=self.steps, seed=seed,
            engine=self.engine, devices=self.devices)
        if len(self.probe.calls) - n0 != 1:
            raise RuntimeError(f"the plan made {len(self.probe.calls) - n0}"
                               f" DES calls; its DES p99 path makes one")
        cha, kw, stats = self.probe.last
        best = plan.best
        verdicts = [dict({f: getattr(v, f) for f in VERDICT_FIELDS},
                         ipc=float(v.ipc[0])) for v in plan.verdicts]
        return dict(
            seed=seed, steps=int(kw["steps"]),
            pick=None if best is None else best.name, verdicts=verdicts,
            lanes={f: np.asarray(getattr(cha, f), np.float32)
                   for f in PLANNER_FIELDS},
            mean=np.asarray(stats.mean_ns, np.float64),
            p99=np.asarray(stats.p99_ns, np.float64))

    def release(self) -> None:
        self.trace = self.probe = None

    @staticmethod
    def finite(answer: dict) -> bool:
        return bool(np.all(np.isfinite(answer["p99"]))
                    and all(np.isfinite(v["access_p99_ns"])
                            and np.isfinite(v["token_p99_ms"])
                            for v in answer["verdicts"]))

    # -- the comparison -------------------------------------------------

    def samples(self, answers, seed: int):
        """Whole jobs and (job, lane) pairs drawn from the seed; the
        pairs hold every lane of the whole jobs."""
        n = len(answers[0]["p99"])
        rng = sample_rng(seed)
        whole = sorted(int(j) for j in rng.choice(
            len(answers), size=min(self.check_jobs, len(answers)),
            replace=False))
        k = min(self.check_lanes, len(answers) * n)
        flat = rng.choice(len(answers) * n, size=k, replace=False)
        pairs = {(int(i) // n, int(i) % n) for i in flat}
        pairs |= {(j, lane) for j in whole for lane in range(n)}
        return whole, sorted(pairs)

    def reference(self, answers, whole, pairs, dtype="float64") -> dict:
        """The references' reading of the same jobs: the plan's lanes,
        the DES statistics at ``pairs`` and the plans of ``whole`` jobs.
        ``dtype`` is the planner's precision; the DES runs in float32,
        or in bfloat16 where the planner does."""
        plan = planner.Plan(self.config, dtype)
        des_dt = "float32" if dtype == "float64" else dtype
        params = {f: [] for f in des.FIELDS}
        for _, lane in pairs:
            for f in des.FIELDS:
                params[f].append(plan.lanes[f][lane]
                                 if f in PLANNER_FIELDS else self.channel[f])
        n = len(plan.lanes["rho"])
        hist = des.simulate(params, [lane for _, lane in pairs],
                            [answers[job]["seed"] for job, _ in pairs],
                            steps=self.steps, chunk=des.adaptive_chunk(n),
                            dtype=des_dt)
        st = des.stats(hist)
        at = {p: i for i, p in enumerate(pairs)}
        plans = {}
        for j in whole:
            idx = [at[(j, lane)] for lane in range(n)]
            plans[j] = plan.compose(st["mean_ns"][idx], st["p99_ns"][idx])
        return dict(lanes=[plan.lanes], mean=st["mean_ns"],
                    p99=st["p99_ns"], plans=plans,
                    verdicts=[p["verdicts"] for p in plans.values()])

    @staticmethod
    def program(answers, whole, pairs) -> dict:
        """The program's reading, in :meth:`reference`'s form."""
        return dict(
            lanes=[a["lanes"] for a in answers],
            mean=np.asarray([answers[j]["mean"][lane] for j, lane in pairs]),
            p99=np.asarray([answers[j]["p99"][lane] for j, lane in pairs]),
            plans={j: dict(verdicts=answers[j]["verdicts"],
                           pick=answers[j]["pick"]) for j in whole},
            verdicts=[a["verdicts"] for a in answers])

    def compare(self, got: dict, ref: dict, limits: dict) -> list[Check]:
        """Every number relative to the reference's; the lane statistics
        relative to the reference's mean latency of that lane, or to one
        histogram bin where that lane recorded nothing."""
        point = max(_rel(lanes[f], ref["lanes"][0][f])
                    for lanes in got["lanes"] for f in PLANNER_FIELDS)
        first = next(iter(ref["plans"].values()))["verdicts"]
        by_name = {v["name"]: v for v in first}
        ipc = area = 0.0
        for verdicts in got["verdicts"]:
            for v in verdicts:
                r = by_name.get(v["name"])
                if r is None:
                    ipc = area = float("inf")
                    continue
                ipc = max(ipc, _rel(v["ipc"], r["ipc"]))
                area = max(area, _rel([v["rel_area"], v["rel_pins"]],
                                      [r["rel_area"], r["rel_pins"]]))
        access = token = 0.0
        verdict_miss = pick_miss = 0
        for j, r in ref["plans"].items():
            g = got["plans"][j]
            pick_miss += g["pick"] != r["pick"]
            verdict_miss += abs(len(g["verdicts"]) - len(r["verdicts"]))
            for gv, rv in zip(g["verdicts"], r["verdicts"]):
                if (gv["name"], gv["meets_slo"]) != (rv["name"],
                                                     rv["meets_slo"]):
                    verdict_miss += 1
                    continue
                access = max(access, _rel(gv["access_p99_ns"],
                                          rv["access_p99_ns"]))
                token = max(token, _rel(
                    [gv["token_p99_ms"], gv["token_mean_ms"]],
                    [rv["token_p99_ms"], rv["token_mean_ms"]]))
        # A lane whose budget ends before its warmup records nothing
        # (mean 0); its scale is one histogram bin.
        scale = np.maximum(ref["mean"], des.BIN_NS)
        values = dict(
            lane_point_dev_max=point,
            lane_mean_dev_max=float(np.max(np.abs(got["mean"] - ref["mean"])
                                           / scale)),
            lane_p99_dev_max=float(np.max(np.abs(got["p99"] - ref["p99"])
                                          / scale)),
            ipc_dev_max=ipc, area_dev_max=area, access_p99_dev_max=access,
            token_dev_max=token, verdict_mismatches=float(verdict_miss),
            pick_mismatches=float(pick_miss))
        return [Check(k, v, float(limits[k])) for k, v in values.items()]

    def check(self, answers, seed: int, limits: dict) -> list[Check]:
        whole, pairs = self.samples(answers, seed)
        return self.compare(self.program(answers, whole, pairs),
                            self.reference(answers, whole, pairs), limits)

    def control(self, answers, seed: int, limits: dict) -> list[Check]:
        """The references in bfloat16 put in the program's place."""
        whole, pairs = self.samples(answers, seed)
        return self.compare(
            self.reference(answers, whole, pairs, "bfloat16"),
            self.reference(answers, whole, pairs), limits)


def make_job(cell) -> Plan:
    return Plan(cell)
