"""The benchmark's own arithmetic on small fixed inputs. Run with
``python3 -m pytest perfbench/tests`` (not part of the repo's tier-1 run).
"""

import json
import os

import pytest

import numpy as np

from perfbench.lib import capture, flops, manifest, stats, traffic

HERE = os.path.dirname(__file__)
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_percentile_is_nearest_rank():
    xs = [15, 20, 35, 40, 50]
    assert stats.percentile(xs, 5) == 15
    assert stats.percentile(xs, 30) == 20
    assert stats.percentile(xs, 40) == 20
    assert stats.percentile(xs, 50) == 35
    assert stats.percentile(xs, 95) == 50
    assert stats.percentile(xs, 100) == 50
    assert stats.percentile(list(range(1, 201)), 95) == 190
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_iqr_share_uses_pythons_exclusive_quartiles():
    # quantiles([1..6], n=4) = [1.75, 3.5, 5.25]
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


@pytest.mark.parametrize("layers,params,gflop", [
    # hand-worked: per layer 4096*4096*2 + 4096*1024*2 + 3*4096*14336 =
    # 218,103,808; head 32768*4096 = 134,217,728; attention at 4096:
    # 2*2*4096*32*128/2 = 33,554,432 FLOPs a token a layer, forward
    (2, 570_445_824, 3.623878656),
    (4, 1_006_669_824, 6.442450944),      # the four-chip cut (PERF.md 7)
])
def test_mistral_counts(layers, params, gflop):
    c = dict(cfg("mistral-7b-v0.3-l2"), num_hidden_layers=layers)
    assert flops.n_params(c) == params
    assert flops.train_flops_per_token(c, 4096) / 1e9 == pytest.approx(gflop)


def test_internlm2_counts():
    c = cfg("internlm2-1.8b")
    # 24 * (2048*2048*2 + 2048*1024*2 + 3*2048*8192) + 92544*2048
    assert flops.matmul_params(c) == 24 * 62_914_560 + 189_530_112
    assert flops.n_params(c) == 1_699_579_904
    # one decoded token that sees 1000 keys: 2 flops a weight, plus
    # 2*2*1000*16*128 a layer of attention
    assert flops.forward_flops(c, 1, 1000) == pytest.approx(
        2 * flops.matmul_params(c) + 24 * 4 * 1000 * 2048)
    # a prompt of 4 tokens: queries see 1+2+3+4 keys
    assert flops.forward_flops(c, 4, 4) == pytest.approx(
        2 * flops.matmul_params(c) * 4 + 24 * 4 * 10 * 2048)


def test_flash_min_seconds_is_the_larger_bound():
    c = cfg("mistral-7b-v0.3-l2")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = flops.flash_train_min_seconds(c, 4096, 4, peaks)
    # 7 products of 2*4096*4096*32*128/2 FLOPs, 2 layers, 4 sequences
    assert r["flops"] == pytest.approx(2 * 4 * 7 * 2 * 4096 * 4096 * 4096 / 2)
    assert r["bound"] == "flops"
    assert r["seconds"] == pytest.approx(r["flops"] / 197e12)


def test_serve_schedule_is_the_mixs_and_tokens_are_the_seeds():
    """Every seed offers the same lengths, order and arrivals (the mix's
    own ``schedule_seed``), ending exactly at the window's end; the seed
    draws the token ids."""
    mix = traffic.load("chat-open")
    a = traffic.serve_requests(mix, 1, 10.0, 92544, 1024)
    b = traffic.serve_requests(mix, 2, 10.0, 92544, 1024)
    assert len(a) == len(b) == round(mix["rate_rps"] * 10.0)
    assert [(r[0], r[2], r[3]) for r in a] == [(r[0], r[2], r[3]) for r in b]
    assert a[-1][0] == pytest.approx(10.0)
    assert any((x[1] != y[1]).any() for x, y in zip(a, b))
    lens = sorted(r[2] for r in a)
    p = mix["prompt_len"]
    assert p["min"] <= lens[0] and lens[-1] <= p["max"]
    assert abs(lens[len(lens) // 2] - p["median"]) < 0.1 * p["median"]
    for _, toks, plen, _ in a:
        assert not toks[plen:].any()


def test_train_rows_are_drawn_from_the_seed_alone():
    """Hand-worked on a tiny vocabulary: a row is the orbit of its first
    token under t -> 7 t + 3 (mod vocab)."""
    rows = traffic.train_batches(5, 8, 4, 11, 2, 3)
    assert rows.shape == (3, 2, 5) and rows.dtype == np.int32
    for r in rows.reshape(-1, 5):
        for t in range(4):
            assert r[t + 1] == (7 * r[t] + 3) % 11
    again = traffic.train_batches(5, 8, 4, 11, 2, 3)
    assert (rows == again).all()
    assert (traffic.train_batches(6, 8, 4, 11, 2, 3) != rows).any()


def test_interval_union():
    assert capture.merge_intervals([(5, 7), (1, 3), (2, 4), (7, 7)]) == \
        [(1, 4), (5, 7)]
    assert capture.measure(capture.merge_intervals([(0, 2), (1, 3)])) == 3


def test_a_capture_is_cut_to_its_stretch():
    """A session left open past the stretch it was opened for: ops are
    clipped to the stretch, and what lies outside it is gone."""
    tracks = {"TPU:0": [(0.0, 10.0, "fusion.1"), (12.0, 20.0, "copy.2"),
                        (30.0, 40.0, "fusion.3")]}
    got = capture.cut(tracks, 5.0, 15.0)
    assert got == {"TPU:0": [(5.0, 10.0, "fusion.1"), (12.0, 15.0, "copy.2")]}
    red = capture.reduce_tracks(got)
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx(8e-6)
    assert capture.cut(tracks, 50.0, 60.0) == {}


def test_reduction_of_a_recorded_capture():
    """A small recorded capture in the profiler's own format: two devices,
    overlapping ops, a collective partly hidden behind compute, a host
    thread that must not count."""
    with open(os.path.join(HERE, "recorded_capture.json")) as f:
        doc = json.load(f)
    tracks = capture.device_tracks(doc)
    assert sorted(tracks) == ["TPU:0", "TPU:1"]
    red = capture.reduce_tracks(tracks)
    # window: 100 .. 1100 us; TPU:0 busy 100-400, 500-900 = 700 us;
    # TPU:1 busy 200-700, 1000-1100 = 600 us; mean 650 us
    assert red["window_s"] == pytest.approx(1000e-6)
    assert red["busy_s"] == pytest.approx(650e-6)
    assert red["busy_s"] <= red["window_s"]
    assert capture.op_seconds(tracks, "flash_(fwd|dqkv)") == \
        pytest.approx((300 + 500) / 2 * 1e-6)
    bd = capture.breakdown(tracks)
    assert bd["device_ops"][0] == ["flash_dqkv", pytest.approx(250e-6)]
    assert len(bd["idle_gaps"]) == 1
    assert bd["idle_gaps"][0][1] == pytest.approx(100e-6)


def _line(metrics, **device):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 1 << 30}
    dev.update(device)
    return {"correct": True, "attempted": 1, "failed": 0,
            "metrics": metrics, "device": dev, "compared": {}}


def test_validator_refuses_what_the_driver_would():
    man = manifest.load()
    cell = man["workloads"][0]["name"]
    e2e = {n: {"value": 1.0, "unit": u}
           for n, u in manifest.expected(man, cell, 0).items()}
    assert manifest.validate_line(_line(e2e), man, cell, 0) == []
    # a bare number, a wrong unit, a missing metric, a foreign metric
    bad = dict(e2e, setup_s=3.0)
    assert manifest.validate_line(_line(bad), man, cell, 0)
    bad = dict(e2e, setup_s={"value": 3.0, "unit": "ms"})
    assert manifest.validate_line(_line(bad), man, cell, 0)
    bad = {k: v for k, v in e2e.items() if k != "setup_s"}
    assert manifest.validate_line(_line(bad), man, cell, 0)
    bad = dict(e2e, other={"value": 1.0, "unit": "s"})
    assert manifest.validate_line(_line(bad), man, cell, 0)
    # a traced run: per-layer metrics, and 0 < busy_s <= window_s
    pl = {n: {"value": 1.0, "unit": u}
          for n, u in manifest.expected(man, cell, 1).items()}
    ok = _line(pl, window_s=2.0, busy_s=1.5)
    assert manifest.validate_line(ok, man, cell, 1) == []
    for dev in ({}, {"window_s": 2.0, "busy_s": 0.0},
                {"window_s": 2.0, "busy_s": 2.5}):
        assert manifest.validate_line(_line(pl, **dev), man, cell, 1)
    over = dict(pl)
    over["train_mfu"] = {"value": 101.0, "unit": "%"}
    assert manifest.validate_line(_line(over, window_s=2.0, busy_s=1.0),
                                  man, cell, 1)


def test_every_cell_has_its_files_and_an_mfu():
    man = manifest.load()
    bench = os.path.dirname(HERE)
    for w in man["workloads"]:
        assert os.path.exists(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json"))
        names = manifest.per_layer(man, w["name"])
        assert any("mfu" in n for n in names), w["name"]
        for n in names:
            assert os.path.exists(os.path.join(bench, "metrics",
                                               n + ".json")), n
        e2e = manifest.end_to_end(man, w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in man["per_layer"]:
            if n == m["name"]:
                assert m["moves"] in e2e
