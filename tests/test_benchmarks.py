"""Smoke the benchmark harness itself: CSV contract + roofline loader."""

import io
import json
import sys

import pytest


def _capture(fn):
    buf = io.StringIO()
    old = sys.stdout
    sys.stdout = buf
    try:
        fn()
    finally:
        sys.stdout = old
    return buf.getvalue()


def test_fig3_csv_contract():
    from benchmarks import fig3_variance
    out = _capture(fig3_variance.main)
    rows = [l for l in out.strip().splitlines() if l]
    assert len(rows) == 3
    for row in rows:
        name, us, derived = row.split(",")
        assert name.startswith("fig3.")
        float(us)
        assert 0.5 < float(derived) < 1.0


def test_table2_csv_contract():
    from benchmarks import table2_designs
    out = _capture(table2_designs.main)
    assert "table2.coaxial-5x.rel_area,0.0,1.166" in out


def test_roofline_loader_tolerates_foreign_json(tmp_path, monkeypatch):
    """int8_proof.json & co. in results/dryrun must not break the loader."""
    from benchmarks import roofline
    monkeypatch.setattr(roofline, "RESULTS_DIR", str(tmp_path))
    with open(tmp_path / "int8_proof.json", "w") as f:
        json.dump({"f32": {}, "int8": {}}, f)
    with open(tmp_path / "cell.json", "w") as f:
        json.dump({"mesh": "16x16", "status": "ok", "chips": 256,
                   "arch": "stablelm-1.6b", "shape": "train_4k",
                   "flops_per_chip": 1e12, "bytes_per_chip": 1e12,
                   "hbm_bytes_per_chip": 5e11,
                   "collectives": {"total": 1e10}, "memory": {},
                   "variant": "baseline"}, f)
    cells = roofline.load_cells("16x16")
    assert len(cells) == 1
    terms = roofline.analyze(cells[0])
    assert terms["dominant"] in ("compute_s", "memory_s", "collective_s")


def test_model_flops_shapes():
    from benchmarks.roofline import model_flops
    train = model_flops("stablelm-1.6b", "train_4k")
    prefill = model_flops("stablelm-1.6b", "prefill_32k")
    decode = model_flops("stablelm-1.6b", "decode_32k")
    assert train > prefill > decode          # 6ND*1M > 2ND*1M > 2ND*128
    # MoE counts active params only
    moe_train = model_flops("olmoe-1b-7b", "train_4k")
    from repro.configs import get_config
    cfg = get_config("olmoe-1b-7b")
    assert moe_train == pytest.approx(
        6 * cfg.active_param_count() * 4096 * 256, rel=1e-6)
