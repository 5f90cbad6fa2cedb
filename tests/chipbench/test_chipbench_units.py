"""chipbench's own arithmetic: the schedule, percentiles and due-time
latencies, the shape functions, the trace reducer. No jax, no program."""
import json
import math

import pytest

from chipbench import schedule, shapes, stats, trace

CHAT = {
    "arrivals": {"kind": "poisson", "rate": 8.0},
    "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 16, "max": 1024},
    "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 8, "max": 512},
}


def test_schedule_is_byte_identical_for_one_seed():
    a = json.dumps(schedule.generate(CHAT, 7, 30.0), sort_keys=True)
    b = json.dumps(schedule.generate(CHAT, 7, 30.0), sort_keys=True)
    assert a == b


def test_schedule_differs_for_another_seed_but_offers_the_same_work():
    a = schedule.generate(CHAT, 7, 30.0)
    b = schedule.generate(CHAT, 2**31 + 12345, 30.0)  # beyond 32 signed bits
    assert [x["t"] for x in a] != [x["t"] for x in b]
    for key in ("prompt_len", "max_new_tokens"):
        assert sorted(x[key] for x in a) == sorted(x[key] for x in b)
    assert len(a) == len(b) == math.ceil(8.0 * 30.0 * 1.05)
    assert a[-1]["t"] == pytest.approx(len(a) / 8.0, rel=0.02)


def test_the_seed_orders_the_schedule_and_draws_the_tokens():
    a, b = (schedule.generate(CHAT, s, 30.0) for s in (7, 8))
    assert [x["prompt_len"] for x in a] != [x["prompt_len"] for x in b]
    ta, tb = (schedule.prompt_tokens(s, a[0], 50257) for s in (7, 8))
    assert len(ta) == len(tb) == a[0]["prompt_len"]
    assert not (ta == tb).all() and ta.max() < 50257
    assert (ta == schedule.prompt_tokens(7, a[0], 50257)).all()


def test_lengths_are_clamped_and_centred():
    s = schedule.generate(CHAT, 3, 30.0)
    p = sorted(x["prompt_len"] for x in s)
    assert p[0] >= 16 and p[-1] <= 1024
    assert p[len(p) // 2] == pytest.approx(256, rel=0.03)


def test_bursts_keep_the_mean_rate_and_crowd_their_windows():
    spec = dict(CHAT, arrivals={"kind": "poisson", "rate": 8.0,
                                "burst_factor": 3.0, "burst_frac": 0.15})
    t = [x["t"] for x in schedule.generate(spec, 5, 60.0)]
    assert t == sorted(t)
    assert t[-1] == pytest.approx(len(t) / 8.0, rel=0.05)
    gaps = sorted(b - a for a, b in zip(t, t[1:]))
    plain = [x["t"] for x in schedule.generate(CHAT, 5, 60.0)]
    plain_gaps = sorted(b - a for a, b in zip(plain, plain[1:]))
    assert gaps[len(gaps) // 10] < plain_gaps[len(gaps) // 10]


def test_closed_pool():
    spec = {"arrivals": {"kind": "closed", "clients": 4, "pool": 32},
            "prompt_len": {"dist": "uniform", "min": 100, "max": 200},
            "output_len": {"dist": "uniform", "min": 4, "max": 8}}
    s = schedule.generate(spec, 1, 10.0)
    assert len(s) == 32 and all(x["t"] == 0.0 for x in s)
    assert sorted(x["prompt_len"] for x in s)[0] >= 100
    assert {x["i"] for x in s} == set(range(32))


def test_train_batches_are_fresh_and_seeded():
    ids0, lab0 = schedule.train_batch(9, 0, 2, 16, 100)
    ids1, _ = schedule.train_batch(9, 1, 2, 16, 100)
    again, _ = schedule.train_batch(9, 0, 2, 16, 100)
    assert (ids0 == again).all() and not (ids0 == ids1).all()
    assert (ids0[:, 1:] == lab0[:, :-1]).all()       # next-token labels
    assert not (ids0[0] == ids0[1]).all()            # rows differ


def test_percentile_and_spread_by_hand():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(v, 50) == 30.0
    assert stats.percentile(v, 95) == pytest.approx(48.0)
    assert stats.percentile([5.0], 95) == 5.0
    # statistics.quantiles(n=4) of 1..6: Q1 = 1.75, Q3 = 5.25, median 3.5
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


def test_latencies_count_from_the_due_instant():
    # due at 1.0, submitted late at 1.4, tokens at 2.0, 2.5, 3.5
    times = [2.0, 2.5, 3.5]
    assert stats.ttft(1.0, times) == pytest.approx(1.0)   # not 0.6
    assert stats.gaps(times) == pytest.approx([0.5, 1.0])
    assert stats.all_gaps([times, [1.0], [4.0, 4.25]]) == pytest.approx(
        [0.5, 1.0, 0.25])
    assert stats.ttft(1.0, []) is None


def test_shape_functions_against_hand_counts():
    # Cerebras-GPT-1.3B as run: h 2048, ff 8192, 24 layers, 50,304 rows
    per_block = 2048 * 6144 + 2048 * 2048 + 2 * 2048 * 8192
    assert per_block == 50_331_648
    mm = shapes.gpt_matmul_params(2048, 8192, 24, 50304)
    assert mm == 24 * per_block + 2048 * 50304 == 1_310_982_144
    assert shapes.gpt_total_params(2048, 8192, 24, 50304, 2048) \
        == 1_418_842_112
    # 13B widths at 8 layers
    assert shapes.gpt_matmul_params(5120, 20480, 8, 50304) \
        == 8 * 12 * 5120 * 5120 + 5120 * 50304
    assert shapes.gpt_total_params(5120, 20480, 8, 50304, 2048) \
        == 3_042_723_840
    # causal attention: S(S+1)/2 pairs, 4d forward and 8d backward each
    assert shapes.causal_pairs(2048) == 2_098_176
    assert shapes.flash_fwd_flops(2048, 16, 128) == 4 * 128 * 16 * 2_098_176
    assert shapes.flash_bwd_flops(2048, 16, 128) \
        == 2 * shapes.flash_fwd_flops(2048, 16, 128)
    per_token = shapes.gpt_train_flops_per_token(2048, 8192, 24, 50304, 16, 2048)
    assert per_token == pytest.approx(
        6 * 1_310_982_144 + 6 * 24 * 2048 * 2049, rel=1e-12)
    assert shapes.kv_bytes_per_token(24, 16, 128, 2) == 196_608
    assert shapes.paged_decode_bytes(1000, 16, 128, 2) == 8_192_000


def _trace():
    ms = 1_000_000
    dev0 = [("fusion.1", 0, 4 * ms), ("all-reduce.1", 3 * ms, 3 * ms),
            ("custom-call.7", 8 * ms, 2 * ms), ("fusion.1", 12 * ms, 4 * ms)]
    dev1 = [("fusion.1", 0, 2 * ms), ("all-reduce.1", 2 * ms, 6 * ms),
            ("fusion.2", 15 * ms, 1 * ms)]
    spans = [("train.step", 0, 11 * ms), ("make_batch", 11 * ms, 1 * ms),
             ("train.step", 12 * ms, 4 * ms)]
    return {"devices": {0: dev0, 1: dev1}, "spans": spans}


def test_trace_reducer_on_a_hand_made_trace():
    t = _trace()
    lo, hi = trace.window_of(t)
    assert (lo, hi) == (0, 16_000_000)
    # device 0 busy [0,6] [8,10] [12,16] = 12 ms; device 1 [0,8] [15,16] = 9
    assert trace.busy_seconds(t, (lo, hi)) == pytest.approx(0.0105)
    assert trace.idle_gaps(t, (lo, hi), 0) == [
        (6_000_000, 8_000_000), (10_000_000, 12_000_000)]
    by_span = dict(map(tuple, trace.idle_by_span(t, (lo, hi), 0)))
    assert by_span == pytest.approx(
        {"train.step": 0.003, "make_batch": 0.001})
    assert trace.kernel_seconds(t, r"^custom-call") == (0.002, 1)
    assert trace.kernel_seconds(t, r"^fusion\.1$") == (0.008, 2)
    top = trace.top_ops(t, limit=2)   # seconds and events per device
    assert top[0] == ["fusion.1 x1", pytest.approx(0.005)]
    assert top[1] == ["all-reduce.1 x1", pytest.approx(0.0045)]


def test_the_traced_window_counts_idle_time_at_its_edges():
    ms = 1_000_000
    t = {"devices": {0: [("%fusion.1 = x", 3 * ms, 4 * ms)]},
         "spans": [("train.step", 1 * ms, 7 * ms), ("make_batch", 8 * ms, 2 * ms)]}
    assert trace.window_of(t) == (1 * ms, 10 * ms)
    assert trace.idle_share(t) == pytest.approx(5 / 9)
    by_span = dict(map(tuple, trace.idle_by_span(t, trace.window_of(t), 0)))
    assert by_span == pytest.approx({"train.step": 0.003, "make_batch": 0.002})


def test_ops_add_up_by_kind_not_by_instruction():
    a = ("%fusion.1242 = (bf16[2048,8192]{1,0:T(8,128)(2,1)S(1)}, "
         "bf16[2048,8192]{1,0:T(8,128)(2,1)S(1)}) fusion(bf16[8192]{0} "
         "%state), kind=kOutput, calls=%fused_computation.7")
    b = a.replace("1242", "1244").replace("computation.7", "computation.9")
    assert trace.op_kind(a) == trace.op_kind(b) \
        == "fusion -> (bf16[2048,8192], bf16[2048,8192])"
    t = {"devices": {0: [(a, 0, 1000), (b, 2000, 3000)]}, "spans": []}
    assert trace.top_ops(t) == [
        ["fusion -> (bf16[2048,8192], bf16[2048,8192]) x2", 4e-6]]


def test_interval_arithmetic():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 4), (6, 9)], 3, 7) == [(3, 4), (6, 7)]
    with pytest.raises(ValueError):
        trace.window_of({"devices": {0: []}, "spans": []})


def test_layer_metric_readers_on_hand_made_facts():
    import os

    from chipbench import harness, peaks
    from chipbench.families import gpt2

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = harness.Cell(bench, "cerebras-gpt-1.3b:chat-poisson")  # no cell yet
    ms = 1_000_000
    kernel = ("%paged_attention.7 = (f32[8,16,1,128]{3,2,1,0}) custom-call("
              "s32[8]{0} %x), custom_call_target=\"tpu_custom_call\"")
    facts = {
        "on_chip": True, "config": cell.config, "family": gpt2,
        "peaks": peaks.peaks_for("TPU v5 lite"), "chips": 1,
        "trace": {"devices": {0: [(kernel, 0, 2 * ms), ("%fusion.1 = x", 2 * ms,
                                                      6 * ms)]}, "spans": []},
        "trace_from_s": 10.0,
        # (start, wall, decode tokens, prefill tokens, live kv tokens)
        "engine_steps": [(9.0, 0.03, 8, 0, 9999), (10.5, 0.03, 8, 0, 4000),
                         (10.6, 0.13, 8, 256, 4167)],
        "counters": {"steps": 3, "decode_tokens": 24, "prefill_tokens": 256},
        "free_blocks_min": 64, "num_blocks": 256, "lag_s": [0.0, 0.001, 0.002],
        "compiles": 0,
    }
    # 8167 live tokens x 196,608 B over 819 GB/s = 1.9606 ms of 2 ms
    assert cell.reader("paged_decode_roofline")(facts) == pytest.approx(
        100 * 8167 * 196608 / 819e9 / 0.002)
    assert cell.reader("engine_step_ms.decode")(facts) == pytest.approx(30.0)
    assert cell.reader("engine_tokens_per_step")(facts) == pytest.approx(280 / 3)
    assert cell.reader("kv_blocks_peak_share")(facts) == pytest.approx(75.0)
    assert cell.reader("device_idle_share.serve")(facts) == pytest.approx(0.0)
    assert cell.reader("generator_lag_p95_ms")(facts) == pytest.approx(1.9)
    off = dict(facts, on_chip=False, trace=None)
    for name in ("paged_decode_roofline", "engine_step_ms.decode",
                 "device_idle_share.serve", "generator_lag_p95_ms"):
        assert cell.reader(name)(off) is None     # nothing from a CPU run
    train = harness.Cell(bench, "train-1p3b-2k")
    tfacts = dict(facts, tokens_per_s=13000.0, seq=2048, step_s=[0.15, 0.16, 0.17])
    flops = 6 * 1_310_982_144 + 6 * 24 * 2048 * 2049
    assert train.reader("train_mfu")(tfacts) == pytest.approx(
        100 * 13000 * flops / 197e12)
    assert train.reader("step_ms.train")(tfacts) == pytest.approx(160.0)
