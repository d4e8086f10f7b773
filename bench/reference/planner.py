"""Plain reference of the capacity planner around the DES.

A straightforward implementation, from the configuration alone, of what
a capacity plan states: it imports nothing of ``repro`` and takes
nothing the program has made.  In order it

1. derives one decode step's demand from the model's shapes: KV and
   weight bytes read per token, KV bytes written, flops and
   instructions, and the demand's anchor on the 12-core baseline
   machine (IPC, non-memory share of CPI, MPKI, write-back ratio);
2. solves the loaded-CPU model for every candidate design: calibration
   on the baseline design, then the damped fixed point of IPC against
   the closed-form queue wait, latency spread and bandwidth floors;
3. builds the candidates (the configuration's designs, a generated CXL
   grid and the measured devices), their tier-split variants with their
   area and pins, and the diurnal trace scaled to the peak utilisation;
4. lays out one DES lane per (variant, epoch, memory tier) at its
   operating point (utilisation, burstiness, population bound, transfer
   time, premium);
5. composes each variant's access p99 and token latency from the lanes'
   DES statistics and the model's floor, judges the SLO, sorts the
   variants cheapest first and picks the first that meets it.

``dtype`` sets the precision every stored value is rounded to:
``float64`` is the reference, ``bfloat16`` the lower-precision control.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

# -- the loaded-CPU model's laws (the paper's Fig-2a calibration) ---------
AVG_Q_COEF_NS = 80.0
RHO_MAX = 0.97
SIGMA_BASE_NS = 75.0
ALPHA_LLC = 0.25
LLC_FIT_FACTOR = 0.05
STREAMING_WS_MB = 1024.0
MIN_CPI_EXEC = 0.02
FP_ITERS = 120
FP_DAMP = 0.5
#: decode demand on the baseline machine
FLOPS_PER_INST = 8.0
CORE_FLOPS_PER_CYCLE = 32.0
MEM_QUEUE_DERATE = 0.6
#: DES lane utilisation is held inside these
LANE_RHO = (0.02, 0.95)


def rounder(dtype):
    """The function that rounds a value to the working precision (and
    back to float64 for the next operation)."""
    dt = jnp.dtype(dtype)
    if dt == np.float64:
        return lambda x: np.asarray(x, np.float64)
    return lambda x: np.asarray(np.asarray(x, np.float64).astype(dt),
                                np.float64)


# -- 1. decode demand ------------------------------------------------------

def demand(config: dict, r) -> dict:
    m, s, sl = config["model"], config["serving"], config["slice"]
    b, batch, ctx = m["bytes_per_value"], s["batch"], s["context"]
    d, f, v, L = (m["hidden_size"], m["intermediate_size"],
                  m["vocab_size"], m["num_hidden_layers"])
    hd = m["head_dim"]
    n_q, n_kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    per_layer = (2 * d * n_q + 2 * d * n_kv + m["mlp_matrices"] * d * f
                 + 2 * d)
    params = L * per_layer + v * d + 2 * d
    if not m["tie_word_embeddings"]:
        params += v * d
    state_rd = r(2.0 * L * n_kv * ctx * b)
    state_wr = r(2.0 * L * n_kv * b)
    weight = r(params * b / batch)
    flops = r(2.0 * params + 4.0 * L * m["num_attention_heads"] * hd * ctx)
    inst = r(flops / FLOPS_PER_INST)
    read = r(state_rd + weight)
    compute_s = r(batch * flops / (sl["sim_cores"] * CORE_FLOPS_PER_CYCLE
                                   * sl["core_clk_ghz"] * 1e9))
    memory_s = r(batch * (read + state_wr) / (sl["ddr5_ch_gbps"] * 1e9)
                 / MEM_QUEUE_DERATE)
    cpi = r((compute_s + memory_s) * sl["core_clk_ghz"] * 1e9
            * sl["sim_cores"] / (batch * inst))
    return dict(
        read=read, state_wr=state_wr, inst=inst,
        mpki=r(read / sl["cache_line_b"] / inst * 1000.0),
        wb=r(state_wr / read),
        exec_frac=r(np.clip(compute_s / (compute_s + memory_s), 0.02, 0.95)),
        ipc=r(np.clip(1.0 / cpi, 0.02, 2.0)),
        ws_mb=r(min((batch * state_rd + params * b) / 1e6, 1e6)))


# -- 2. the loaded-CPU model ------------------------------------------------

def _design_arrays(designs) -> dict:
    keys = ("dram_channels", "links", "link_rd_gbps", "link_wr_gbps",
            "iface_lat_ns", "llc_mb_per_core")
    return {k: np.asarray([float(x[k]) for x in designs]) for k in keys}


class CpuModel:
    """The fixed-point CPU model for one workload, vectorised over
    designs (each a dict of :func:`_design_arrays`)."""

    def __init__(self, config: dict, wl: dict, r):
        self.sl, self.wl, self.r = config["slice"], wl, r
        self.n = float(self.sl["sim_cores"])

    def mpki(self, sys):
        wl, r = self.wl, self.r
        scale = r((2.0 / sys["llc_mb_per_core"]) ** ALPHA_LLC)
        mpki = (np.full_like(scale, wl["mpki"])
                if wl["ws_mb"] >= STREAMING_WS_MB else r(wl["mpki"] * scale))
        fits = wl["ws_mb"] * self.n <= sys["llc_mb_per_core"] * self.n
        return np.where(fits, r(wl["mpki"] * LLC_FIT_FACTOR), mpki)

    def eff(self):
        wb = self.wl["wb"]
        return self.r(0.92 - 0.18 * self.r(wb / (1.0 + wb)))

    def traffic(self, ipc, mpki):
        read = self.r(ipc * self.sl["core_clk_ghz"] * self.n * mpki / 1000.0
                      * self.sl["cache_line_b"])
        return read, self.r(read * self.wl["wb"])

    def latency(self, sys, read, write, iface):
        """(mean latency, sigma, DRAM utilisation)."""
        wl, r, sl = self.wl, self.r, self.sl
        ch_bw = r(sl["ddr5_ch_gbps"] * self.eff())
        rho = r((read + write) / (sys["dram_channels"] * ch_bw))
        outstanding = r(self.n * sl["max_mlp"] / sys["dram_channels"])
        rc = np.clip(rho, 0.0, RHO_MAX)
        w_open = r(wl["eta"] * wl["kappa"] ** 2 * AVG_Q_COEF_NS * rc
                   / (1.0 - rc))
        cap = r(outstanding * sl["cache_line_b"] / ch_bw
                * np.minimum(1.0, rho * wl["kappa"]))
        w_dram = np.minimum(w_open, cap)
        cxl = sys["links"] > 0
        link_rd = np.maximum(sys["links"] * sys["link_rd_gbps"], 1e-9)
        rx = np.clip(r(read / link_rd), 0.0, RHO_MAX)
        svc = r(sl["cache_line_b"] / np.maximum(sys["link_rd_gbps"], 1e-9))
        w_link = np.where(cxl, r(wl["kappa"] ** 2 * svc * rx
                                 / (2.0 * (1.0 - rx))), 0.0)
        queue = r(w_dram + w_link)
        sigma = r(np.sqrt(SIGMA_BASE_NS ** 2 + queue ** 2))
        return r(sl["dram_service_ns"] + queue + iface), sigma, rho

    def cpi_bw(self, sys, mpki):
        sl, r = self.sl, self.r
        rd = r(mpki / 1000.0 * sl["cache_line_b"])
        wr = r(rd * self.wl["wb"])
        k = self.n * sl["core_clk_ghz"]
        cpi = r((rd + wr) * k / (sys["dram_channels"] * sl["ddr5_ch_gbps"]
                                 * self.eff()))
        cxl = sys["links"] > 0
        link_rd = np.maximum(sys["links"] * sys["link_rd_gbps"], 1e-9)
        link_wr = np.maximum(sys["links"] * sys["link_wr_gbps"], 1e-9)
        cpi = np.maximum(cpi, np.where(cxl, r(rd * k / link_rd), 0.0))
        return np.maximum(cpi, np.where(cxl, r(wr * k / link_wr), 0.0))

    def mlp_eff(self, mlp_cal, rho):
        boost = 1.0 + self.wl["pf_boost"] * (1.0 - np.clip(rho, 0.0, 1.0))
        return np.clip(self.r(mlp_cal * boost), 1.0, self.sl["max_mlp"])

    def calibrate(self, base):
        """(cpi_exec, mlp_cal) that make the baseline meet the anchor."""
        wl, r = self.wl, self.r
        mpki = self.mpki(base)
        read, write = self.traffic(wl["ipc"], mpki)
        lat, sigma, rho = self.latency(base, read, write,
                                       base["iface_lat_ns"])
        l_eff = r((lat + wl["gamma"] * sigma) * self.sl["core_clk_ghz"])
        budget = r((1.0 - wl["exec_frac"]) / wl["ipc"])
        mlp_base = np.clip(r(mpki / 1000.0 * l_eff
                             / np.maximum(budget, 1e-9)),
                           1.0, self.sl["max_mlp"])
        mlp_cal = r(mlp_base / (1.0 + wl["pf_boost"]
                                * (1.0 - np.clip(rho, 0.0, 1.0))))
        cpi_exec = np.maximum(r(1.0 / wl["ipc"] - mpki / 1000.0 * l_eff
                                / mlp_base), MIN_CPI_EXEC)
        return cpi_exec, mlp_cal

    def solve(self, sys, base) -> np.ndarray:
        """IPC of each design at the converged operating point."""
        wl, r = self.wl, self.r
        cpi_exec, mlp_cal = self.calibrate(base)
        mpki = self.mpki(sys)
        floor = self.cpi_bw(sys, mpki)
        ipc = np.full(len(sys["links"]), wl["ipc"])
        for _ in range(FP_ITERS):
            read, write = self.traffic(ipc, mpki)
            lat, sigma, rho = self.latency(sys, read, write,
                                           sys["iface_lat_ns"])
            cpi_mem = r(mpki / 1000.0 * r((lat + wl["gamma"] * sigma)
                                          * self.sl["core_clk_ghz"])
                        / self.mlp_eff(mlp_cal, rho))
            cpi = np.maximum(r(cpi_exec + cpi_mem), floor)
            ipc = r((1.0 - FP_DAMP) * ipc + FP_DAMP / cpi)
        return ipc


# -- 3. candidates, variants, trace -----------------------------------------

def design_cost(config: dict, channels, links, llc) -> tuple:
    """(relative area, relative pins) of a pure design at full scale."""
    a, sl = config["area"], config["slice"]
    scale = sl["full_cores"] // sl["sim_cores"]
    cores = sl["full_cores"]

    def die(llc_mb, ddr, x8):
        return (cores * a["core"] + llc_mb * a["l3_per_mb"]
                + ddr * a["ddr_ch"] + x8 * a["pcie_x8"])

    base = die(cores * a["base_llc_mb_per_core"], a["full_ddr_channels"], 0)
    ddr = 0.0 if links > 0 else float(channels * scale)
    x8 = float(links * scale)
    pins = ddr * a["ddr_pins"] + x8 * a["pcie_x8_pins"]
    return (die(cores * llc, ddr, x8) / base,
            pins / (a["full_ddr_channels"] * a["ddr_pins"]))


def candidates(config: dict) -> list[dict]:
    """Baseline, the configuration's designs, the generated CXL grid,
    the measured devices; the first of a name wins."""
    s, sl = config["serving"], config["slice"]
    out = {}
    for d in config["designs"]:
        out.setdefault(d["name"], dict(d))
    for ch in s["channels"]:
        for llc in s["llc_mb"]:
            for prem in s["premium_ns"]:
                name = f"cxl-{ch}ch-llc{llc:g}-{prem:g}ns"
                if name in out:
                    continue
                area, pins = design_cost(config, ch, ch, llc)
                out[name] = dict(
                    name=name, dram_channels=ch, links=ch,
                    link_rd_gbps=sl["cxl_x8_rd_gbps"],
                    link_wr_gbps=sl["cxl_x8_wr_gbps"], iface_lat_ns=prem,
                    llc_mb_per_core=llc, rel_area=area, rel_pins=pins)
    if s["include_measured"]:
        for d in config["measured_devices"]:
            out.setdefault(d["name"], dict(d))
    return list(out.values())


def per_channel_gbps(config: dict, channels, links, link_rd) -> float:
    ddr = config["slice"]["ddr5_ch_gbps"]
    return min(ddr, links * link_rd / channels) if links else ddr


def variants(config: dict, designs) -> list[dict]:
    """One variant per (design, distinct DDR-tier channel count):
    ``lanes`` are (channels, GB/s per channel, premium)."""
    sl = config["slice"]
    out = []
    for d in designs:
        ch, links = d["dram_channels"], d["links"]
        if links == 0:
            # Direct DDR: every channel on the DDR tier, no split.
            out.append(dict(name=d["name"], design=d["name"],
                            lanes=[(ch, sl["ddr5_ch_gbps"], 0.0)],
                            rel_area=d["rel_area"], rel_pins=d["rel_pins"]))
            continue
        splits = []
        for s in config["serving"]["tier_splits"]:
            hot = int(round(s * ch))
            if hot not in splits:
                splits.append(hot)
        for hot in splits:
            cold = ch - hot
            links_cold = max(1, math.ceil(links * cold / ch)) if cold else 0
            lanes = []
            if hot:
                lanes.append((hot, sl["ddr5_ch_gbps"], 0.0))
            if cold:
                lanes.append((cold, per_channel_gbps(
                    config, cold, links_cold, d["link_rd_gbps"]),
                    d["iface_lat_ns"]))
            if hot == 0:
                area, pins = d["rel_area"], d["rel_pins"]
            else:
                llc = d["llc_mb_per_core"]
                h = design_cost(config, hot, 0, llc)
                c = design_cost(config, 0, links_cold, llc)
                n = design_cost(config, 0, 0, llc)
                area, pins = h[0] + c[0] - n[0], h[1] + c[1]
            split = hot / ch
            out.append(dict(
                name=f"{d['name']}+tier{split:g}" if split else d["name"],
                design=d["name"], lanes=lanes, rel_area=area,
                rel_pins=pins))
    return out


def trace_epochs(config: dict) -> list[tuple[float, float]]:
    """(requests per second, burstiness) of each epoch of a sinusoidal
    day: rate from the trough to the peak, burstiness with it."""
    t = config["trace"]
    out = []
    for i in range(t["n_epochs"]):
        s = 0.5 - 0.5 * math.cos(2.0 * math.pi * (i + 0.5) / t["n_epochs"])
        out.append((t["peak_rps"] * (t["trough_frac"]
                                     + (1.0 - t["trough_frac"]) * s),
                    t["kappa_base"] + (t["kappa_peak"] - t["kappa_base"])
                    * s))
    return out


# -- 4., 5. lanes and the plan ------------------------------------------------

class Plan:
    """Everything of a plan that does not depend on the DES: the lanes to
    simulate and each design's IPC.  :meth:`compose` finishes it from
    the lanes' DES statistics."""

    def __init__(self, config: dict, dtype="float64"):
        r = self.r = rounder(dtype)
        self.config = config
        s, sl = config["serving"], config["slice"]
        self.scale = sl["full_cores"] // sl["sim_cores"]
        self.demand = dm = demand(config, r)
        wl = dict(config["workload"], ipc=dm["ipc"], mpki=dm["mpki"],
                  wb=dm["wb"], exec_frac=dm["exec_frac"],
                  ws_mb=dm["ws_mb"])
        designs = candidates(config)
        model = CpuModel(config, wl, r)
        ipc = model.solve(_design_arrays(designs),
                          _design_arrays(config["designs"][:1]))
        self.ipc = {d["name"]: float(x) for d, x in zip(designs, ipc)}
        self.variants = [dict(v, rel_area=float(r(v["rel_area"])),
                              rel_pins=float(r(v["rel_pins"])))
                         for v in variants(config, designs)]

        bytes_per_req = r(s["tokens_per_req"] * (dm["read"]
                                                 + dm["state_wr"]))
        epochs = trace_epochs(config)
        cap_max = max(sum(n * per for n, per, _ in v["lanes"]) * self.scale
                      for v in self.variants)
        peak = max(rps for rps, _ in epochs) * bytes_per_req / 1e9
        factor = s["peak_util"] * cap_max / peak
        self.epochs = [(rps * factor, kappa) for rps, kappa in epochs]
        lanes = []
        for v in self.variants:
            total = sum(n for n, _, _ in v["lanes"])
            for rps, kappa in self.epochs:
                offered = rps * bytes_per_req / 1e9
                for n, per, prem in v["lanes"]:
                    rho = offered * (n / total) / (n * per * self.scale)
                    lanes.append(dict(
                        rho=min(max(rho, LANE_RHO[0]), LANE_RHO[1]),
                        kappa=kappa,
                        outstanding=sl["max_mlp"] * sl["sim_cores"] / total,
                        t_xfer_ns=sl["cache_line_b"] / per,
                        cxl_lat_ns=prem))
        self.lanes = {k: r([x[k] for x in lanes]) for k in lanes[0]}

    def compose(self, mean: np.ndarray, p99: np.ndarray) -> dict:
        """The plan's verdicts (cheapest first) and its pick, from each
        lane's DES mean and p99 latency (ns, in lane order)."""
        r, s, sl = self.r, self.config["serving"], self.config["slice"]
        dm = self.demand
        in_flight = sl["max_mlp"] * sl["sim_cores"] * self.scale
        lines = r(s["batch"] * dm["read"] / sl["cache_line_b"])
        waves = max(float(r(lines / in_flight)), 1.0)
        out, lane = [], 0
        for v in self.variants:
            total = sum(n for n, _, _ in v["lanes"])
            acc99 = accmu = 0.0
            for _ in self.epochs:
                cells = range(lane, lane + len(v["lanes"]))
                lane += len(v["lanes"])
                shares = [n / total for n, _, _ in v["lanes"]]
                acc99 = max(acc99, float(r(sum(
                    sh * p99[c] for sh, c in zip(shares, cells)))))
                accmu = max(accmu, float(r(sum(
                    sh * mean[c] for sh, c in zip(shares, cells)))))
            ipc = self.ipc[v["design"]]
            t_model = float(r(s["batch"] * dm["inst"] / (
                ipc * sl["core_clk_ghz"] * 1e9 * sl["sim_cores"]
                * self.scale)))
            tok99 = max(float(r(waves * acc99 * 1e-9)), t_model)
            tokmu = max(float(r(waves * accmu * 1e-9)), t_model)
            out.append(dict(
                name=v["name"], rel_area=float(v["rel_area"]),
                rel_pins=float(v["rel_pins"]), ipc=ipc,
                access_p99_ns=acc99, token_p99_ms=tok99 * 1e3,
                token_mean_ms=tokmu * 1e3,
                meets_slo=bool(tok99 * 1e3 <= s["slo_p99_ms"])))
        out.sort(key=lambda v: (v["rel_area"], v["rel_pins"], v["name"]))
        pick = next((v["name"] for v in out if v["meets_slo"]), None)
        return dict(verdicts=out, pick=pick)
