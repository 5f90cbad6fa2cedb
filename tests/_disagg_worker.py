"""Disaggregated-serving worker process (driven by tests/test_disagg.py
and benchmarks/serving_throughput.py --disagg).

One real prefill OR decode worker: connects to the driver's
TCPKVStore, builds a deterministic tiny model (paddle.seed(0) +
LlamaConfig.tiny — identical weights in every process, so greedy
outputs are token-exact across the pools), and runs a
:class:`DisaggServer` over a journaled worker. The kill-mid-handoff
test schedules a ``kill`` fault at ``handoff.transfer`` in the prefill
worker (PADDLE_CHAOS env transport) so the process dies with a partial
transfer in the store — the decode side must discard it and the
router's journal recovery must requeue the request.

env:
  DISAGG_ROLE         — "prefill" | "decode"
  DISAGG_STORE_PORT   — the driver's TCPStoreServer port
  DISAGG_MODEL_JSON   — LlamaConfig kwargs as JSON (the bench passes
                        ITS config so the disagg row measures the same
                        model as the unified baseline; default: tiny)
  JAX_PLATFORMS       — honored when set; default cpu (a worker is a
                        CPU process: one process for each chip, and the
                        parent that spawns it may hold the chip)
  DISAGG_CONTRACT_RANK/_WORLD — flight-recorder contract topology
                        (default: role rank in a 1+1 pair; REQUIRED
                        when running >1 worker per role)
  DISAGG_WORKER_ID    — this worker's id (store namespace)
  DISAGG_JOURNAL_DIR  — journal directory (read by the router on death)
  DISAGG_DECODE_IDS   — comma-separated decode channels (prefill role)
  DISAGG_BUDGET       — serve-loop wall budget in seconds (default 120)
  DISAGG_N_PARTS      — fixed part count per transfer (deterministic
                        chaos indexing; default: size-based split)
  DISAGG_CHUNK        — prefill_chunk for both roles (default: whole-
                        prompt prefill with DISAGG_PAD)
  DISAGG_PAD          — prompt_pad (default 24)
  DISAGG_MAX_LEN      — engine max_len (default 32)
  DISAGG_BLOCKS       — engine num_blocks (default 16)
  DISAGG_BATCH        — engine max_batch (default 2)
  DISAGG_TRACE_DUMP   — non-empty: write this process's obs trace-ring
                        dump (JSON list of span dicts) to the path on
                        serve-loop exit, for cross-process stitching
  PADDLE_CHAOS        — optional fault schedule (the victim only)
  PADDLE_LOCK_SANITIZER — non-empty: run under the graft-race lockdep
                        sanitizer (utils/locks.py) and assert zero
                        lock-order violations on clean exit
  PADDLE_LEAK_SANITIZER — non-empty: run under the graft-own resource
                        ledger (utils/resources.py); on clean exit
                        leak_check() must find ZERO outstanding KV
                        blocks / slots / handoff holds — a leak names
                        its acquisition site and fails the worker
"""
import json
import os

# pin CPU only when the driver didn't choose a platform
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import obs  # noqa: E402
from paddle_tpu.distributed.store import TCPKVStore  # noqa: E402
from paddle_tpu.inference.disagg import (  # noqa: E402
    DecodeWorker,
    DisaggServer,
    PrefillWorker,
)
from paddle_tpu.inference.serving import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402


def main():
    # graft-race slow lane: PADDLE_LOCK_SANITIZER=1 runs the whole
    # worker under TracedLock (lockdep) — an inverted acquisition
    # order anywhere in prefill/decode raises LockOrderViolation
    # in-process, and the exit assertion below makes a recorded
    # violation a nonzero worker exit the driving test sees
    sanitize = bool(os.environ.get("PADDLE_LOCK_SANITIZER"))
    if sanitize:
        from paddle_tpu.utils.locks import instrument_locks, violation_count
        instrument_locks()
    # graft-own slow lane: PADDLE_LEAK_SANITIZER=1 mirrors every
    # BlockManager acquire/release (and the slot/handoff lifecycle)
    # in a ResourceLedger; instrument BEFORE the factory so the
    # engine's manager is built already wrapped
    leak_sanitize = bool(os.environ.get("PADDLE_LEAK_SANITIZER"))
    if leak_sanitize:
        from paddle_tpu.utils import resources as _res
        _res.instrument_resources()
    paddle.seed(0)
    role = os.environ["DISAGG_ROLE"]
    max_len = int(os.environ.get("DISAGG_MAX_LEN", "32"))
    model_json = os.environ.get("DISAGG_MODEL_JSON")
    if model_json:
        cfg = LlamaConfig(**json.loads(model_json))
    else:
        cfg = LlamaConfig.tiny()
        if max_len > cfg.max_position_embeddings:
            cfg = LlamaConfig.tiny(max_position_embeddings=max_len)
    model = LlamaForCausalLM(cfg)
    blocks = int(os.environ.get("DISAGG_BLOCKS", "16"))
    chunk = os.environ.get("DISAGG_CHUNK")

    max_batch = int(os.environ.get("DISAGG_BATCH", "2"))

    def factory():
        kw = dict(max_batch=max_batch, max_len=max_len, block_size=8,
                  num_blocks=blocks,
                  role="prefill_only" if role == "prefill"
                  else "decode_only",
                  # ISSUE 10: disagg workers inherit the async
                  # host/device pipeline through their factory
                  overlap=bool(os.environ.get("DISAGG_OVERLAP")))
        if chunk:
            kw["prefill_chunk"] = int(chunk)
        else:
            kw["prompt_pad"] = int(os.environ.get("DISAGG_PAD", "24"))
        return ContinuousBatchingEngine(model, **kw)

    store = TCPKVStore("127.0.0.1",
                       int(os.environ["DISAGG_STORE_PORT"]))
    wid = os.environ["DISAGG_WORKER_ID"]
    journal_dir = os.environ["DISAGG_JOURNAL_DIR"]
    if role == "prefill":
        sender_kwargs = {}
        n_parts = os.environ.get("DISAGG_N_PARTS")
        if n_parts:
            sender_kwargs["n_parts"] = int(n_parts)
        worker = PrefillWorker(
            wid, factory, store,
            os.environ["DISAGG_DECODE_IDS"].split(","),
            journal_dir=journal_dir, sender_kwargs=sender_kwargs)
    else:
        worker = DecodeWorker(
            wid, factory, store, journal_dir=journal_dir,
            steps_per_pump=int(
                os.environ.get("DISAGG_STEPS_PER_PUMP", "1")))
    obs.set_process_label(f"{role}:{wid}")
    crank = os.environ.get("DISAGG_CONTRACT_RANK")
    try:
        DisaggServer(
            store, worker,
            contract_rank=None if crank is None else int(crank),
            contract_world=int(
                os.environ.get("DISAGG_CONTRACT_WORLD", "2")),
        ).serve(deadline=float(os.environ.get("DISAGG_BUDGET", "120")))
    finally:
        dump_path = os.environ.get("DISAGG_TRACE_DUMP")
        if dump_path:
            with open(dump_path, "w", encoding="utf-8") as fh:
                json.dump(obs.ring().dump(), fh)
    if sanitize:
        n = violation_count()
        assert n == 0, f"lock sanitizer recorded {n} violation(s)"
        print("lock-sanitizer: clean", flush=True)
    if leak_sanitize:
        eng = worker.supervisor.engine
        if eng.prefix_cache is not None:
            eng.prefix_cache.clear()
        led = _res.current()
        led.verify(eng.manager)   # free + referenced == pool total
        led.leak_check()          # raises naming acquisition sites
        print("leak-sanitizer: clean", flush=True)


if __name__ == "__main__":
    main()
