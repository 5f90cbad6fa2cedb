"""The toy benchmark the tests run: one toy configuration of the gpt2
family and three toy traffic mixes, added the way a later PR adds them —
data files under a benchmark path and entries here, no edit to a file of
``chipbench/``."""
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _m(name, unit, source, layer, moves, better="lower"):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves}


BENCH = {
    "command": ["python3", "-m", "chipbench"],
    "paths": ["chipbench", "tests/chipbench"],
    "run_seconds": 1,
    "configs": [{"name": "toy-gpt", "source": "none (a test's toy)",
                 "file": "tests/chipbench/configs/toy-gpt.json",
                 "reduced": [], "why": "CPU tests"}],
    "workloads": [
        {"name": "toy-train", "config": "toy-gpt", "traffic": "toy-train",
         "chips": 1, "why": "job kind train"},
        {"name": "toy-open", "config": "toy-gpt", "traffic": "toy-open",
         "chips": 1, "why": "job kind serve_open"},
        {"name": "toy-closed", "config": "toy-gpt", "traffic": "toy-closed",
         "chips": 1, "why": "job kind serve_closed"},
    ],
    "end_to_end": [
        {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.03, "source": "host_clock", "workloads": ["toy-train"]},
        {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.03, "source": "host_clock",
         "workloads": ["toy-open", "toy-closed"]},
        {"name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["toy-open"]},
        {"name": "itl_p99_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["toy-open"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"},
    ],
    "per_layer": [
        _m("compiles_in_window.train", "count", "program_counter",
           "compiled step", "train_tokens_per_s"),
        _m("step_ms.train", "ms", "host_clock", "compiled step",
           "train_tokens_per_s"),
        _m("train_mfu", "%", "host_clock", "models", "train_tokens_per_s",
           "higher"),
        _m("device_idle_share.train", "%", "device_trace", "device",
           "train_tokens_per_s"),
        _m("compiles_in_window.serve", "count", "program_counter",
           "compiled step", "serve_tokens_per_s"),
        _m("engine_tokens_per_step", "tokens/step", "program_counter",
           "engine", "serve_tokens_per_s", "higher"),
        _m("kv_blocks_peak_share", "%", "program_counter", "cache",
           "serve_tokens_per_s", "higher"),
        _m("device_idle_share.serve", "%", "device_trace", "device",
           "serve_tokens_per_s"),
        _m("engine_step_ms.decode", "ms", "host_clock", "engine",
           "itl_p99_ms"),
        _m("generator_lag_p95_ms", "ms", "host_clock", "entry",
           "ttft_p50_ms"),
        _m("paged_decode_roofline", "%", "device_trace", "kernels",
           "itl_p99_ms", "higher"),
    ],
}
