"""CPU-ONLY serving count gate: N concurrent HTTP requests through the
continuous-batching runtime at toy widths, on the CPU.

NOT a chip benchmark and never to be run on the chip: it pins
``JAX_PLATFORMS=cpu`` and re-executes itself as a child process, and a chip
belongs to one process. Its counts (tokens, batches, hits, compiles) are
usable; its milliseconds time XLA's CPU backend and say nothing about a TPU.
On the chip run ``python chip_smoke.py`` (repo root); the benchmark with
cells that replaces this tool is ROADMAP S1.

Prints ONE JSON line — always: on any failure or timeout a structured record
with value 0 and an "error" field is emitted instead of a traceback.

Usage::

    python tools/bench_serve.py                  # 16 requests, 8-way concurrency
    python tools/bench_serve.py --requests 32 --concurrency 16 --max-tokens 24
    python tools/bench_serve.py --replicas 2     # router front tier over 2 CPU
                                                 # replicas; the JSON line adds
                                                 # request_share/failovers/rerouted
                                                 # + /fleet/slo readouts (fleet
                                                 # availability, TTFT vs the
                                                 # objective, burn rates)
    python tools/bench_serve.py --prefix-share 0.75
                                                 # 75% of requests reuse one long
                                                 # common prefix; the JSON line's
                                                 # prefix_cache_hit_rate and
                                                 # cached_tokens track the win
    python tools/bench_serve.py --long-prompt-mix --prefill-chunk 64
                                                 # a few multi-thousand-token
                                                 # prompts injected into a stream
                                                 # of short chatty requests; the
                                                 # JSON line folds in client p99
                                                 # TTFT + p99 inter-token (decode
                                                 # stall) — rerun with
                                                 # --prefill-chunk 0 and the
                                                 # chunked-vs-monolithic tail is
                                                 # one flag flip to compare.
                                                 # (64 is the CPU-smoke sweet
                                                 # spot; 256-512 suits real TPU
                                                 # runs. A mixed step's cost
                                                 # scales with the tokens
                                                 # actually fed)
    python tools/bench_serve.py --mesh-shape 2,4 # tensor-parallel sharded
                                                 # engine on a dp=2 x tp=4 mesh
                                                 # of virtual CPU devices —
                                                 # weights + KV pool sharded on
                                                 # tp; JSON adds mesh_shape/
                                                 # tp_degree (composes with
                                                 # --prefill-chunk and
                                                 # --prefix-share)
    python tools/bench_serve.py --adapters 3 --tenant-mix
                                                 # multi-tenant multi-LoRA arm:
                                                 # 3 rank-4 adapters registered
                                                 # in the engine's adapter pool;
                                                 # 3 of 4 requests decode with
                                                 # an adapter (round-robin), the
                                                 # 4th rides the base model in
                                                 # the SAME batches; --tenant-mix
                                                 # spreads requests over three
                                                 # tenants. JSON adds
                                                 # adapter_hit_rate /
                                                 # adapter_evictions + a
                                                 # multi_lora record and a
                                                 # per-tenant requests/shed
                                                 # breakdown. --adapters 6
                                                 # overcommits the 4-slot pool
                                                 # so LRU hot-load/evict churn
                                                 # shows up in the numbers. The
                                                 # default (no-adapter) arm is
                                                 # the one gated against
                                                 # tools/BENCH_BASELINE.json
    python tools/bench_serve.py --replicas 3 --drain-mid-run
                                                 # halfway through the request
                                                 # stream, drain one replica via
                                                 # the router admin plane (POST
                                                 # /replicas/drain → DELETE) —
                                                 # the JSON line adds drained_ok
                                                 # plus the failovers/hedges the
                                                 # churn caused, so elasticity
                                                 # shows up in the bench
                                                 # trajectory
    python tools/bench_serve.py --replicas 2 --swap-mid-run
                                                 # halfway through the request
                                                 # stream, roll a new base
                                                 # checkpoint across the fleet
                                                 # (POST /admin/weights/rollout:
                                                 # drain -> swap -> canary ->
                                                 # rejoin, one replica at a
                                                 # time) while requests keep
                                                 # flowing — the JSON line adds
                                                 # a rollout record (wall_s,
                                                 # streams_lost which must be 0,
                                                 # p99 TTFT during the swap
                                                 # window) so zero-downtime is a
                                                 # gateable number
    python tools/bench_serve.py --replicas 2 --hedge-after-ms 250
                                                 # arm request hedging: a stream
                                                 # (or batch request) with no
                                                 # first token inside the budget
                                                 # races a shadow on the next
                                                 # replica; JSON adds hedges
                                                 # (total fired/capped)
    PDNLP_TPU_FLIGHT_RECORDER=0 python tools/bench_serve.py
                                                 # flight recorder disabled:
                                                 # rerun without the env var
                                                 # and diff value/tails — the
                                                 # recorder-overhead A/B. The
                                                 # JSON line always carries
                                                 # flight_recorder (on/off) +
                                                 # flight_events, and an
                                                 # `attribution` record with
                                                 # per-phase p50/p99 (inbox/queue/
                                                 # admission_gate/prefill/
                                                 # chunk_stall/migration_wait/
                                                 # decode) so a BENCH_r*
                                                 # regression localizes to a
                                                 # phase, not just a number
    python tools/bench_serve.py --surge 1,6,8 --autoscale 1,3
                                                 # closed-loop demo: open-loop
                                                 # arrivals ramp 1 -> 6 req/s
                                                 # over 8s (flat shoulders
                                                 # before/after) while the
                                                 # in-process autoscaler
                                                 # watches /fleet/slo +
                                                 # /replicas and drives the
                                                 # admin plane inside a 1..3
                                                 # replica envelope. 1 in 4
                                                 # requests is best_effort —
                                                 # at the max envelope the
                                                 # brownout ladder sheds them
                                                 # while interactive TTFT
                                                 # holds. JSON adds surge
                                                 # (per-phase p99 TTFT, shed/
                                                 # rejected counts, SLO burn
                                                 # trajectory) + autoscale
                                                 # (scale events, final
                                                 # replica count)
    python tools/bench_serve.py --multi-turn 4   # conversation-lifetime arm:
                                                 # 16 conversations of 4 chat
                                                 # turns each through
                                                 # /v1/chat/completions, turn 1
                                                 # opening with a long (64-tok)
                                                 # user message. The engine runs
                                                 # with a deliberately small
                                                 # device KV pool + a host spill
                                                 # tier (host_kv_blocks), so
                                                 # between a conversation's
                                                 # turns the OTHER conversations
                                                 # churn its cached blocks out
                                                 # to host RAM — turn k's
                                                 # history promotes back H2D
                                                 # ahead of prefill. JSON adds a
                                                 # multi_turn record (per-turn
                                                 # cache-hit rate, TTFT turn 1
                                                 # vs turn k, spill/promote
                                                 # counts + promote bandwidth)
                                                 # that tools/bench_compare.py
                                                 # gates: hit rate > 0 on turns
                                                 # >= 2 and turn-k TTFT below
                                                 # turn-1 TTFT
    python tools/bench_serve.py --disagg 2,2 --long-prompt-mix --prefill-chunk 64
                                                 # disaggregated prefill/decode
                                                 # engine: prompt work on a
                                                 # 2-device prefill stage,
                                                 # decode on a 2-device decode
                                                 # stage, KV blocks migrating
                                                 # between stage pools. JSON
                                                 # adds a disagg record with
                                                 # per-stage TTFT / inter-token
                                                 # tails + migration counts —
                                                 # compare against
                                                 # --mesh-shape 1,4 (shared
                                                 # pool) with one flag flip
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

METRIC = "serve_smoke_requests_per_sec"
UNIT = "requests/sec (tiny-llama CPU serving smoke)"
RUN_TIMEOUT_S = float(os.environ.get("PDNLP_BENCH_SERVE_TIMEOUT", 600))


def _fail(reason: str) -> None:
    print(json.dumps({"metric": METRIC, "value": 0.0, "unit": UNIT, "error": reason[:2000]}))
    sys.exit(1)


def _parse_mesh_shape():
    """``--mesh-shape R,C`` (dp x tp) or ``--mesh-shape T`` (tp only)."""
    if "--mesh-shape" not in sys.argv:
        return None
    raw = sys.argv[sys.argv.index("--mesh-shape") + 1]
    parts = [int(x) for x in raw.split(",")]
    if len(parts) == 1:
        parts = [1, parts[0]]
    if len(parts) != 2 or any(p < 1 for p in parts):
        _fail(f"--mesh-shape must be T or R,C with positive degrees, got {raw!r}")
    return tuple(parts)


def _parse_disagg():
    """``--disagg P,D``: device counts for the prefill / decode stages."""
    if "--disagg" not in sys.argv:
        return None
    raw = sys.argv[sys.argv.index("--disagg") + 1]
    parts = [int(x) for x in raw.split(",")]
    if len(parts) != 2 or any(p < 1 for p in parts):
        _fail(f"--disagg must be P,D with positive device counts, got {raw!r}")
    return tuple(parts)


def _parse_surge():
    """``--surge R1,R2,T``: open-loop arrival rate ramping R1 -> R2 req/s
    over T seconds (flat R1 shoulders of T/2 before and after)."""
    if "--surge" not in sys.argv:
        return None
    raw = sys.argv[sys.argv.index("--surge") + 1]
    parts = [float(x) for x in raw.split(",")]
    if len(parts) != 3 or parts[0] <= 0 or parts[1] <= 0 or parts[2] <= 0:
        _fail(f"--surge must be R1,R2,T with positive values, got {raw!r}")
    return tuple(parts)


def _parse_autoscale():
    """``--autoscale MIN,MAX``: run the in-process autoscaler in the loop."""
    if "--autoscale" not in sys.argv:
        return None
    raw = sys.argv[sys.argv.index("--autoscale") + 1]
    parts = [int(x) for x in raw.split(",")]
    if len(parts) != 2 or not 1 <= parts[0] <= parts[1]:
        _fail(f"--autoscale must be MIN,MAX with 1 <= MIN <= MAX, got {raw!r}")
    return tuple(parts)


def _force_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    mesh = _parse_mesh_shape()
    disagg = _parse_disagg()
    if mesh is not None and disagg is not None:
        _fail("--mesh-shape and --disagg are mutually exclusive (a disagg "
              "stage is itself a sharded device group)")
    n_dev = None
    if mesh is not None:
        n_dev = mesh[0] * mesh[1]
    elif disagg is not None:
        n_dev = disagg[0] + disagg[1]
    if n_dev is not None:
        # the host-device count must be pinned BEFORE jax loads; the virtual
        # CPU devices back the sharded/disagg engine's meshes. Appended so
        # any user-supplied XLA flags survive (last flag wins on duplicates)
        extra = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{extra} --xla_force_host_platform_device_count={n_dev}".strip())
    else:
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    import jax

    jax.config.update("jax_platforms", "cpu")


def _arg(flag: str, default: int) -> int:
    if flag in sys.argv:
        return int(sys.argv[sys.argv.index(flag) + 1])
    return default


def _farg(flag: str, default: float) -> float:
    if flag in sys.argv:
        return float(sys.argv[sys.argv.index(flag) + 1])
    return default


def run() -> None:
    _force_cpu()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    import http.client
    import threading

    from paddlenlp_tpu.experimental import InferenceEngine
    from paddlenlp_tpu.serving import MetricsRegistry, SchedulerConfig, ServingServer
    from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

    n_requests = _arg("--requests", 16)
    concurrency = _arg("--concurrency", 8)
    max_tokens = _arg("--max-tokens", 16)
    n_replicas = _arg("--replicas", 1)
    drain_mid_run = "--drain-mid-run" in sys.argv
    swap_mid_run = "--swap-mid-run" in sys.argv
    hedge_after_ms = _farg("--hedge-after-ms", 0.0)
    prefix_share = _farg("--prefix-share", 0.0)
    surge = _parse_surge()
    autoscale = _parse_autoscale()
    if autoscale and not surge:
        _fail("--autoscale needs --surge (the control loop reacts to the ramp)")
    if autoscale:
        # the fleet starts at the envelope floor; the autoscaler grows it
        n_replicas = autoscale[0]
    if drain_mid_run and n_replicas < 2:
        _fail("--drain-mid-run needs --replicas >= 2 (one replica must survive)")
    if swap_mid_run and n_replicas < 2:
        _fail("--swap-mid-run needs --replicas >= 2 (the rollout swaps one "
              "replica at a time while the rest keep serving)")
    # --surge R1,R2,T: precompute the open-loop arrival schedule (the ramp
    # integrates the linear rate; flat R1 shoulders bracket it so the JSON
    # can report p99 TTFT before/during/after)
    surge_schedule = []  # (t_offset_s, phase, priority)
    if surge:
        r1, r2, ramp_s = surge
        shoulder = max(ramp_s / 2.0, 2.0)
        t = 0.0
        i = 0
        while t < shoulder:
            surge_schedule.append((t, "before"))
            t += 1.0 / r1
        ramp_t0 = t
        while t - ramp_t0 < ramp_s:
            frac = (t - ramp_t0) / ramp_s
            surge_schedule.append((t, "during"))
            t += 1.0 / (r1 + (r2 - r1) * frac)
        tail_t0 = t
        while t - tail_t0 < shoulder:
            surge_schedule.append((t, "after"))
            t += 1.0 / r1
        # 1 in 4 requests is best_effort: the shed class the brownout ladder
        # drops first when the envelope pins
        surge_schedule = [(off, phase, "best_effort" if i % 4 == 3 else "interactive")
                          for i, (off, phase) in enumerate(surge_schedule)]
        n_requests = len(surge_schedule)
    multi_turn = _arg("--multi-turn", 0)
    if multi_turn:
        if multi_turn < 2:
            _fail(f"--multi-turn must be >= 2 turns, got {multi_turn}")
        if surge or drain_mid_run or swap_mid_run or "--long-prompt-mix" in sys.argv \
                or _parse_disagg() is not None:
            _fail("--multi-turn composes with --replicas/--prefill-chunk/"
                  "--mesh-shape only (not --surge/--drain-mid-run/"
                  "--swap-mid-run/--long-prompt-mix/--disagg)")
    n_adapters = _arg("--adapters", 0)
    tenant_mix = "--tenant-mix" in sys.argv
    tenants = ("acme", "globex", "initech")
    long_mix = "--long-prompt-mix" in sys.argv
    n_long = _arg("--long-prompts", 2)
    long_tokens = _arg("--long-prompt-tokens", 2048)
    prefill_chunk = _arg("--prefill-chunk", 0)
    mesh_shape = _parse_mesh_shape()
    disagg = _parse_disagg()
    if not 0.0 <= prefix_share <= 1.0:
        _fail(f"--prefix-share must be in [0, 1], got {prefix_share}")
    # 24 tokens = 6 full blocks at block_size=4: a warm hit skips all of them
    shared_prefix = [9, 8, 7, 6, 5, 4, 3, 2] * 3

    # mesh/disagg runs use a head count the tp axes can divide (8 heads x
    # head_dim 8 instead of 4 x 16) so the KV pool and attention actually shard
    n_heads, n_kv = (8, 8) if (mesh_shape or disagg) else (4, 2)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112, num_hidden_layers=2,
                      num_attention_heads=n_heads, num_key_value_heads=n_kv,
                      max_position_embeddings=4096 if long_mix else 256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    model = LlamaForCausalLM.from_config(cfg, seed=0)

    if long_mix:
        # bigger blocks so a multi-thousand-token prompt fits a sane table
        eng_kw = dict(max_batch_size=4, block_size=32, num_blocks=352,
                      max_blocks_per_seq=96, decode_steps=4)
        # over-capacity long prompts would finish 'capacity' with zero tokens
        # over a normal 200 stream — the mix would silently measure nothing
        cap = eng_kw["max_blocks_per_seq"] * eng_kw["block_size"]
        if long_tokens + max_tokens > cap:
            _fail(f"--long-prompt-tokens {long_tokens} + --max-tokens {max_tokens} "
                  f"exceeds the long-mix engine's per-seq KV capacity ({cap} tokens)")
    else:
        eng_kw = dict(max_batch_size=4, block_size=4, num_blocks=256,
                      max_blocks_per_seq=32, decode_steps=4)
    # --multi-turn K: conversations of K chat turns. The device pool is
    # deliberately SMALL relative to the conversations' total cached KV, so
    # finished turns' blocks spill to the host tier under LRU pressure and
    # turn k's history must promote back — the hierarchy is what's measured.
    n_convs = 0
    mt_open_tokens, mt_user_tokens = 64, 4
    if multi_turn:
        n_convs = n_requests
        eng_kw = dict(max_batch_size=4, block_size=4, num_blocks=160,
                      max_blocks_per_seq=48, decode_steps=4,
                      enable_prefix_cache=True, host_kv_blocks=2048)
        # final-turn render: [u]+64+[sep] opener, then per prior turn an
        # assistant ([a]+completion+[sep]) + user ([u]+4+[sep]) pair, + the
        # trailing assistant marker — must fit per-seq KV with the completion
        final_prompt = (2 + mt_open_tokens) \
            + (multi_turn - 1) * (2 + max_tokens + 2 + mt_user_tokens) + 1
        cap = eng_kw["max_blocks_per_seq"] * eng_kw["block_size"]
        if final_prompt + max_tokens > cap:
            _fail(f"--multi-turn {multi_turn} x --max-tokens {max_tokens}: "
                  f"final-turn prompt (~{final_prompt}) + completion exceeds "
                  f"the per-seq KV capacity ({cap} tokens)")
        n_requests = n_convs * multi_turn  # throughput counts every turn
    if prefill_chunk:
        eng_kw["prefill_chunk_tokens"] = prefill_chunk
    if mesh_shape:
        eng_kw["mesh_shape"] = mesh_shape
    if disagg:
        eng_kw["disagg_stages"] = disagg
    # which stream positions carry a long prompt (spread through the run so
    # chatty decodes are always in flight when one lands)
    long_every = max(n_requests // max(n_long, 1), 1)
    # request 0 is the warmup; long prompts land at i = 1, 1+long_every, ...
    # (the i-1 anchor keeps long_every == 1 meaningful: requests 1..n_long)
    is_long = (lambda i: long_mix and i >= 1 and (i - 1) % long_every == 0
               and (i - 1) // long_every < n_long)
    # what the schedule actually issues (i ranges over 0..n_requests-1, so
    # --long-prompts close to --requests can't all land); report THIS count
    n_long_issued = sum(1 for i in range(n_requests) if is_long(i))

    # --adapters N: N deterministic rank-4 LoRA adapters served from the
    # engine's slot pool. pool_slots caps at 4 so N > 4 overcommits the pool
    # and the run exercises LRU hot-load/evict churn, not just warm gathers.
    adapter_registries: list = []
    adapter_pool_slots = min(n_adapters, 4) if n_adapters else 0

    def adapter_source(idx: int) -> dict:
        import numpy as _np

        from paddlenlp_tpu.serving.tenancy.adapters import adapter_dims_from_config

        rng = _np.random.default_rng(1000 + idx)
        src = {}
        for proj, (d_in, d_out) in adapter_dims_from_config(cfg).items():
            src[proj] = {
                "A": rng.standard_normal(
                    (cfg.num_hidden_layers, d_in, 4)).astype(_np.float32) * 0.02,
                "B": rng.standard_normal(
                    (cfg.num_hidden_layers, 4, d_out)).astype(_np.float32) * 0.02,
            }
        return src

    def make_engine():
        # one shared model (read-only params), one engine per replica — except
        # under --swap-mid-run: the hot-swap rebinds model.params, so a shared
        # model object would leak the new weights into replicas that have not
        # swapped yet; each replica gets its own identically-seeded model
        mdl = LlamaForCausalLM.from_config(cfg, seed=0) if swap_mid_run else model
        kw = dict(eng_kw)
        if n_adapters:
            from paddlenlp_tpu.serving.tenancy import AdapterRegistry

            reg = AdapterRegistry(config=cfg, max_rank=4,
                                  pool_slots=adapter_pool_slots)
            for a in range(n_adapters):
                reg.add(f"bench-ad-{a}", adapter_source(a))
            adapter_registries.append(reg)
            kw["adapter_registry"] = reg
        return InferenceEngine(mdl, **kw)

    # --swap-mid-run: commit the two checkpoints the rollout needs BEFORE the
    # timed window (v1 is the new weights, v0 the rollback target) so the
    # measured wall clock holds only the drain/swap/canary/rejoin walk itself
    swap_ckpts: dict = {}
    if swap_mid_run:
        import tempfile

        from paddlenlp_tpu.trainer.unified_checkpoint import save_unified_checkpoint

        ck_root = tempfile.mkdtemp(prefix="bench_swap_ck_")
        for ver, seed in (("v0", 0), ("v1", 1)):
            path = os.path.join(ck_root, ver)
            save_unified_checkpoint(
                path, LlamaForCausalLM.from_config(cfg, seed=seed), None)
            swap_ckpts[ver] = path

    registry = MetricsRegistry()
    fleet = server = None
    if n_replicas > 1 or autoscale:
        # multi-replica mode: the timed window goes through the router front
        # tier, so the measured path includes routing + SSE passthrough
        from paddlenlp_tpu.serving.router import launch_fleet

        fleet = launch_fleet(
            n_replicas, make_engine, policy="least_loaded", router_registry=registry,
            poll_interval_s=0.2,
            hedge_after_s=hedge_after_ms / 1e3 if hedge_after_ms > 0 else None,
            scheduler_config=SchedulerConfig(max_inflight=2 * n_requests))
        port = fleet.router_port
    else:
        server = ServingServer(make_engine(), registry=registry,
                               scheduler_config=SchedulerConfig(max_inflight=2 * n_requests))
        port = server.start_in_thread()

    # warmup: one request pays the jit compiles so the timed window measures
    # steady-state serving, not tracing
    def one_request(i: int, stats: dict):
        t0 = time.time()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=RUN_TIMEOUT_S)
        # --long-prompt-mix: a few multi-thousand-token prompts ride a stream
        # of short chatty requests (the worst decode-stall workload). Unique
        # deterministic token streams keep the prefix cache out of the picture.
        # --prefix-share P: fraction P of requests open with one long common
        # prefix (a system prompt stand-in), so the prefix cache has something
        # to hit; the unique tail keeps every request distinct. The golden-
        # ratio stride spreads the P fraction evenly even for small N
        if i == -1:
            # dedicated long-prompt warmup: same length as the measured long
            # prompts but a distinct token stream (no prefix-cache overlap)
            prompt = [(5 + 3 * j) % 90 + 1 for j in range(long_tokens)]
        elif i < -1:
            # chatty warmup riders: distinct short prompts, never the shared
            # prefix (they must not pre-warm the measured prefix cache)
            prompt = [78 - i, 6, 7]
        elif is_long(i):
            prompt = [(7 * i + 3 * j) % 90 + 1 for j in range(long_tokens)]
        elif (i * 0.6180339887) % 1.0 < prefix_share:
            prompt = shared_prefix + [5 + i % 8, 6, 7]
        else:
            prompt = [5 + i % 8, 6, 7]
        payload = {"prompt": prompt, "max_tokens": max_tokens, "stream": True}
        # 3 of 4 requests decode with an adapter (round-robin over the pool),
        # the 4th stays on the base model — mixed batches are the point; the
        # warmup (i == 0) carries an adapter so the gathered-delta program
        # compiles outside the measured window
        if n_adapters and i >= 0 and i % 4 != 3:
            payload["adapter_id"] = f"bench-ad-{i % n_adapters}"
        if tenant_mix and i >= 0:
            payload["tenant"] = tenants[i % len(tenants)]
        body = json.dumps(payload)
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"request {i}: HTTP {resp.status}")
        n_toks, ttft, last_t = 0, None, None
        gaps = []
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: ") or line == b"data: [DONE]":
                if line == b"data: [DONE]":
                    break
                continue
            ev = json.loads(line[len(b"data: "):])
            if "token" in ev["choices"][0]:
                now = time.time()
                if ttft is None:
                    ttft = now - t0
                else:
                    gaps.append(now - last_t)
                last_t = now
                n_toks += 1
        conn.close()
        stats["ttft"].append(ttft if ttft is not None else float("nan"))
        stats["tokens"] += n_toks
        if not is_long(i):
            # the chatty requests are the decode-stall victims: their token
            # gaps are the p99 the long-prompt mix is trying to protect
            stats["gaps_short"].extend(gaps)

    warm = {"ttft": [], "tokens": 0, "gaps_short": []}
    one_request(0, warm)
    if long_mix:
        # compile the long-prefill path (mixed-step jit / long prefill bucket)
        # outside the measured window: the tail comparison is about steady-state
        # scheduling, not one-time XLA compiles. Short chatty streams ride along
        # so mixed-step shapes with 1..3 concurrent decode rows (every segment
        # bucket the measured window will see) compile here too, not inside a
        # measured decode gap
        riders = [threading.Thread(
            target=one_request, args=(-2 - r, {"ttft": [], "tokens": 0, "gaps_short": []}))
            for r in range(3)]
        for t in riders:
            t.start()
        one_request(-1, warm)
        for t in riders:
            t.join()

    # --autoscale: the in-process provisioner + control loop, started after
    # warmup so compile stalls don't read as overload
    scaler = provisioner = None
    if autoscale:
        from paddlenlp_tpu.serving.router.autoscaler import (
            Autoscaler,
            AutoscalerPolicy,
            InProcessProvisioner,
        )

        provisioner = InProcessProvisioner(
            make_engine, replica_kw=dict(
                scheduler_config=SchedulerConfig(max_inflight=2 * n_requests)))
        scaler = Autoscaler(
            ("127.0.0.1", port), provisioner,
            policy=AutoscalerPolicy(
                min_replicas=autoscale[0], max_replicas=autoscale[1],
                scale_up_queue_depth=2.0, scale_up_kv_utilization=0.7,
                scale_down_queue_depth=0.5, scale_down_kv_utilization=0.3,
                hysteresis_up=2, hysteresis_down=4,
                cooldown_up_s=2.0, cooldown_down_s=4.0,
                max_step_up=1, drain_deadline_s=15.0),
            interval_s=0.5)
        scaler.start()

    stats = {"ttft": [], "tokens": 0, "gaps_short": []}
    surge_stats = {"shed": 0, "shed_best_effort": 0, "rejected": 0,
                   "phase_ttft": {"before": [], "during": [], "after": []},
                   "interactive_ttft": []}
    slo_samples: list = []
    lock = threading.Lock()
    errors: list = []
    sem = threading.Semaphore(concurrency)

    # --drain-mid-run: halfway through the request stream, drain the last
    # replica through the router's admin plane (the same POST /replicas/drain
    # → poll → DELETE sequence an autoscaler would issue) while the remaining
    # requests keep flowing — elasticity becomes part of the measured window.
    drain_result: dict = {}

    def drain_worker():
        victim = f"127.0.0.1:{fleet.ports[-1]}"
        t_drain = time.time()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("POST", "/replicas/drain",
                         body=json.dumps({"id": victim, "deadline_s": 30.0}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            conn.close()
            if resp.status != 200:
                drain_result["drained_ok"] = False
                drain_result["error"] = f"drain POST: HTTP {resp.status}"
                return
            # the poller drives drain progress; wait for "drained" then DELETE
            drained = False
            deadline = time.time() + 60
            while time.time() < deadline and not drained:
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
                conn.request("GET", "/replicas")
                doc = json.loads(conn.getresponse().read())
                conn.close()
                drained = any(r["id"] == victim and (r.get("drain") or {}).get("drained")
                              for r in doc.get("replicas", []))
                if not drained:
                    time.sleep(0.1)
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("DELETE", f"/replicas/{victim}" + ("" if drained else "?force=1"))
            resp = conn.getresponse()
            resp.read()
            conn.close()
            drain_result["drained_ok"] = bool(drained and resp.status == 200)
            drain_result["drain_wall_s"] = round(time.time() - t_drain, 3)
            drain_result["drained_replica"] = victim
        except Exception as e:
            drain_result["drained_ok"] = False
            drain_result["error"] = repr(e)

    # --swap-mid-run: halfway through the request stream, roll the v1
    # checkpoint across every replica via the router's rollout orchestrator
    # (drain -> swap -> canary -> health-gated rejoin, one replica at a time)
    # while the remaining requests keep flowing. ttft_timed pairs each TTFT
    # with its absolute first-token timestamp so the record can isolate the
    # tail measured INSIDE the swap window.
    rollout_result: dict = {}
    ttft_timed: list = []  # (abs first-token time, ttft_s)

    def swap_worker():
        rollout_result["t0"] = time.time()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=RUN_TIMEOUT_S)
            conn.request("POST", "/admin/weights/rollout",
                         body=json.dumps({"ckpt_dir": swap_ckpts["v1"],
                                          "rollback_ckpt_dir": swap_ckpts["v0"],
                                          "drain_deadline_s": 60.0,
                                          "rejoin_timeout_s": 60.0,
                                          "wait": True}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            doc = json.loads(resp.read())
            conn.close()
            ro = doc.get("rollout") or {}
            rollout_result["status"] = ro.get("status")
            rollout_result["wall_s"] = ro.get("wall_s")
            rollout_result["replicas_swapped"] = len(ro.get("completed") or [])
            rollout_result["abort_reason"] = ro.get("abort_reason")
            rollout_result["ok"] = bool(
                resp.status == 200 and ro.get("status") == "done")
        except Exception as e:
            rollout_result["ok"] = False
            rollout_result["error"] = repr(e)
        rollout_result["t1"] = time.time()

    def worker(i: int):
        local = {"ttft": [], "tokens": 0, "gaps_short": []}
        t_req = time.time()
        try:
            one_request(i, local)
        except Exception as e:
            with lock:
                errors.append(f"req {i}: {e!r}")
            return
        finally:
            sem.release()
        with lock:
            stats["ttft"].extend(local["ttft"])
            stats["tokens"] += local["tokens"]
            stats["gaps_short"].extend(local["gaps_short"])
            if swap_mid_run:
                ttft_timed.extend((t_req + v, v) for v in local["ttft"])

    # --multi-turn: per-conversation history (token-id assistant content, the
    # exact sampled ids — re-encoding text could diverge from the cache) and
    # per-turn readouts. conv_hist is only touched by that conversation's
    # worker thread within a turn wave, and waves are join()-separated.
    conv_hist: list = [[] for _ in range(n_convs)]
    turn_rows: list = [[] for _ in range(multi_turn)]  # (ttft, cached, prompt)

    def chat_turn(conv: int, turn: int):
        t0_turn = time.time()
        if turn == 0:
            # a long opener (system-prompt stand-in): the span turns 2..K
            # re-use from cache instead of re-prefilling
            content = [(11 * conv + 5 + j) % 88 + 5 for j in range(mt_open_tokens)]
        else:
            content = [(11 * conv + 7 * turn + j) % 88 + 5
                       for j in range(mt_user_tokens)]
        messages = conv_hist[conv] + [{"role": "user", "content": content}]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=RUN_TIMEOUT_S)
        conn.request("POST", "/v1/chat/completions",
                     body=json.dumps({"messages": messages,
                                      "max_tokens": max_tokens, "stream": True,
                                      "conversation": f"bench-conv-{conv}"}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"conv {conv} turn {turn}: HTTP {resp.status}")
        ttft, toks, usage = None, [], {}
        while True:
            line = resp.readline()
            if not line or line.strip() == b"data: [DONE]":
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[len(b"data: "):])
            delta = (ev.get("choices") or [{}])[0].get("delta") or {}
            if "token" in delta:
                if ttft is None:
                    ttft = time.time() - t0_turn
                toks.append(delta["token"])
            if ev.get("usage"):
                usage = ev["usage"]
        conn.close()
        conv_hist[conv] = messages + [{"role": "assistant", "content": toks}]
        with lock:
            stats["ttft"].append(ttft if ttft is not None else float("nan"))
            stats["tokens"] += len(toks)
            turn_rows[turn].append((ttft if ttft is not None else 0.0,
                                    int(usage.get("cached_tokens", 0)),
                                    int(usage.get("prompt_tokens", 0))))

    def conv_worker(conv: int, turn: int):
        try:
            chat_turn(conv, turn)
        except Exception as e:
            with lock:
                errors.append(f"conv {conv} turn {turn}: {e!r}")
        finally:
            sem.release()

    def surge_request(i: int, phase: str, priority: str):
        """One open-loop surge request: sheds (503 overloaded_shed) and
        backpressure rejections are COUNTED, not errors — graceful
        degradation is the behavior under measurement."""
        t_start = time.time()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=RUN_TIMEOUT_S)
            conn.request("POST", "/v1/completions",
                         body=json.dumps({"prompt": [5 + i % 8, 6, 7],
                                          "max_tokens": max_tokens,
                                          "stream": True, "priority": priority}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raw = resp.read()
                conn.close()
                try:
                    etype = json.loads(raw).get("error", {}).get("type", "")
                except ValueError:
                    etype = ""
                with lock:
                    # a replica-level shed reaches the client directly
                    # (overloaded_shed) or wrapped by the router after every
                    # candidate shed it (no_replica_available); the replicas'
                    # shed counter in the JSON is the authoritative total
                    if etype == "overloaded_shed" or (
                            etype == "no_replica_available"
                            and priority == "best_effort"):
                        surge_stats["shed"] += 1
                        if priority == "best_effort":
                            surge_stats["shed_best_effort"] += 1
                    else:
                        surge_stats["rejected"] += 1
                return
            ttft, n_toks = None, 0
            while True:
                line = resp.readline()
                if not line or line.strip() == b"data: [DONE]":
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                ev = json.loads(line[len(b"data: "):])
                if "token" in ev["choices"][0]:
                    if ttft is None:
                        ttft = time.time() - t_start
                    n_toks += 1
            conn.close()
            with lock:
                stats["tokens"] += n_toks
                if ttft is not None:
                    stats["ttft"].append(ttft)
                    surge_stats["phase_ttft"][phase].append(ttft)
                    if priority == "interactive":
                        surge_stats["interactive_ttft"].append(ttft)
        except Exception as e:
            with lock:
                errors.append(f"surge req {i}: {e!r}")

    t0 = time.time()
    threads = []
    drain_thread = None
    swap_thread = None
    if surge:
        # SLO burn trajectory: sampled like an on-call dashboard would, once
        # a second over the whole run (router mode only)
        stop_sampler = threading.Event()

        def slo_sampler():
            while not stop_sampler.is_set():
                try:
                    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
                    conn.request("GET", "/fleet/slo")
                    doc = json.loads(conn.getresponse().read())
                    conn.close()
                    windows = doc.get("windows") or {}
                    if windows:
                        w = windows[min(windows, key=lambda k: int(k.rstrip("s")))]
                        slo_samples.append({
                            "t_s": round(time.time() - t0, 2),
                            "availability_burn": round(
                                w["availability_burn_rate"], 3),
                            "ttft_burn": round(w["ttft_burn_rate"], 3)})
                except Exception:
                    pass
                stop_sampler.wait(1.0)

        sampler = None
        if fleet is not None:
            sampler = threading.Thread(target=slo_sampler, daemon=True)
            sampler.start()
        # open loop: each request fires at its scheduled offset regardless of
        # how many are still in flight — arrival pressure is the experiment
        for i, (off, phase, priority) in enumerate(surge_schedule):
            delay = t0 + off - time.time()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=surge_request, args=(i, phase, priority))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        if scaler is not None:
            # post-surge settle window: give the loop a chance to observe the
            # calm, scale back down, AND finalize the drain (removal happens
            # on a later tick than the down decision) before the verdict
            settle_deadline = time.time() + 15.0
            while time.time() < settle_deadline:
                if any(a == "drained" for _t, a, _d in scaler.events):
                    break
                time.sleep(0.25)
            scaler.stop()
        if sampler is not None:
            stop_sampler.set()
            sampler.join(timeout=5)
    elif multi_turn:
        # turn waves: every conversation's turn t runs (concurrency-bounded)
        # before any turn t+1 starts, so between a conversation's consecutive
        # turns the other conversations' prefills churn the device cache —
        # the forced-pressure schedule that makes the host tier earn the hit
        for turn in range(multi_turn):
            wave = []
            for c in range(n_convs):
                sem.acquire()
                th = threading.Thread(target=conv_worker, args=(c, turn))
                th.start()
                wave.append(th)
            for th in wave:
                th.join()
    else:
        for i in range(n_requests):
            sem.acquire()
            if drain_mid_run and drain_thread is None and i >= n_requests // 2:
                drain_thread = threading.Thread(target=drain_worker, daemon=True)
                drain_thread.start()
            if swap_mid_run and swap_thread is None and i >= n_requests // 2:
                swap_thread = threading.Thread(target=swap_worker, daemon=True)
                swap_thread.start()
            t = threading.Thread(target=worker, args=(i,))
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
    if drain_thread is not None:
        drain_thread.join(timeout=90)
    if swap_thread is not None:
        swap_thread.join(timeout=RUN_TIMEOUT_S)
    dt = time.time() - t0

    # scrape /metrics over HTTP (the same path a real Prometheus takes) BEFORE
    # shutdown, while the end-of-run engine state is still live. In router
    # mode the HTTP plane serves the paddlenlp_router_* series; the per-replica
    # serving planes are read straight from the in-process registries.
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    scraped = resp.read().decode()
    conn.close()
    if resp.status != 200:
        _fail(f"/metrics scrape failed: HTTP {resp.status}")
    replica_expositions = [r.expose() for r in fleet.registries()] if fleet is not None \
        else [scraped]
    if provisioner is not None:
        # autoscaler-provisioned replicas live outside the launch-time fleet;
        # their serving planes fold into the same readouts
        replica_expositions += [s.registry.expose()
                                for s in provisioner.servers.values()]
    fleet_slo = None
    if fleet is not None:
        # fleet SLO plane: federated availability + TTFT burn rates, scraped
        # the same way an on-call dashboard would
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/fleet/slo")
        resp = conn.getresponse()
        slo_raw = resp.read()
        conn.close()
        if resp.status == 200:
            fleet_slo = json.loads(slo_raw)
    final_replicas = None
    if scaler is not None:
        scaler.stop()  # no-op when the settle window already stopped it
        final_replicas = len(fleet.router.pool)
    if fleet is not None:
        fleet.shutdown(drain_timeout_s=10)
    else:
        server.shutdown(drain_timeout_s=10)
    if provisioner is not None:
        provisioner.close()

    if errors:
        _fail(f"{len(errors)}/{n_requests} requests failed: {errors[:3]}")
    if swap_mid_run and not rollout_result.get("ok"):
        _fail(f"--swap-mid-run rollout did not land: {rollout_result}")
    ttfts = sorted(stats["ttft"])
    p = lambda q: ttfts[min(int(q * len(ttfts)), len(ttfts) - 1)] if ttfts else 0.0

    from paddlenlp_tpu.observability import histogram_quantile, parse_prometheus_text

    replica_fams = [parse_prometheus_text(t) for t in replica_expositions]

    def scalar_sum(name):
        return sum((f[name].value() or 0.0) for f in replica_fams if name in f)

    def labeled_sum(name):
        # sum across every labelset (Family.value() is unlabeled-only)
        total = 0.0
        for f in replica_fams:
            fam = f.get(name)
            if fam is None:
                continue
            for (sample_name, _labels), v in fam.samples.items():
                if sample_name == name:
                    total += v
        return total

    def quantile_max(name, q):
        # worst replica's quantile: merging bucket vectors across registries
        # buys nothing a tail-latency readout cares about
        vals = [histogram_quantile(f[name], q) for f in replica_fams if name in f]
        return max(vals) if vals else 0.0

    record = {
        "metric": METRIC,
        "value": round(n_requests / dt, 3),
        "unit": UNIT,
        "n_requests": n_requests,
        "concurrency": concurrency,
        "max_tokens": max_tokens,
        "replicas": n_replicas,
        "wall_s": round(dt, 3),
        "tokens_per_sec": round(stats["tokens"] / dt, 1),
        "p50_ttft_ms": round(p(0.50) * 1e3, 1),
        "p99_ttft_ms": round(p(0.99) * 1e3, 1),
        "server_ttft_p50_ms": round(
            quantile_max("paddlenlp_serving_ttft_seconds", 0.5) * 1e3, 1),
        "p99_inter_token_ms": round(
            quantile_max("paddlenlp_serving_inter_token_seconds", 0.99) * 1e3, 1),
        "kv_utilization": round(
            scalar_sum("paddlenlp_serving_kv_utilization") / max(len(replica_fams), 1), 4),
        "kv_free_blocks": scalar_sum("paddlenlp_serving_kv_free_blocks"),
        "preemptions": scalar_sum("paddlenlp_serving_preemptions_total"),
        "tokens_generated": scalar_sum("paddlenlp_serving_tokens_generated_total"),
        "mesh_shape": f"{mesh_shape[0]}x{mesh_shape[1]}" if mesh_shape else "1x1",
        "tp_degree": mesh_shape[1] if mesh_shape else 1,
        "prefix_share": prefix_share,
        # hit rate over every request the engines saw (timed + warmup)
        "prefix_cache_hit_rate": round(
            scalar_sum("paddlenlp_serving_prefix_cache_hits_total") / (n_requests + 1), 4),
        "cached_tokens": int(scalar_sum("paddlenlp_serving_prefix_cache_cached_tokens_total")),
    }
    # per-phase latency attribution (worst replica's quantiles, like the other
    # tail readouts): a BENCH_r* regression now names the phase that moved
    from paddlenlp_tpu.observability import RECORDER

    attr_name = "paddlenlp_serving_latency_attribution_seconds"
    attribution = {}
    for phase in ("inbox", "queue", "admission_gate", "promote_wait", "prefill",
                  "chunk_stall", "migration_wait", "decode"):
        p50 = max([histogram_quantile(f[attr_name], 0.5, phase=phase)
                   for f in replica_fams if attr_name in f] or [0.0])
        p99 = max([histogram_quantile(f[attr_name], 0.99, phase=phase)
                   for f in replica_fams if attr_name in f] or [0.0])
        attribution[phase] = {"p50_ms": round(p50 * 1e3, 1),
                              "p99_ms": round(p99 * 1e3, 1)}
    record["attribution"] = attribution
    # goodput ledger readout: what fraction of the run's device positions was
    # useful work, the waste decomposition, compile count and the host-gap
    # tail — the fields tools/bench_compare.py gates regressions on
    def labeled_by(name, label):
        out = {}
        for f in replica_fams:
            fam = f.get(name)
            if fam is None:
                continue
            for (_sample, labels), v in fam.samples.items():
                key = dict(labels).get(label)
                if key is not None:
                    out[key] = out.get(key, 0.0) + v
        return out

    gp_fed = scalar_sum("paddlenlp_serving_fed_tokens_total")
    gp_useful = scalar_sum("paddlenlp_serving_useful_tokens_total")
    record["goodput"] = {
        "ratio": round(gp_useful / gp_fed, 6) if gp_fed else 1.0,
        "fed_tokens": int(gp_fed),
        "useful_tokens": int(gp_useful),
        "wasted_tokens": {k: int(v) for k, v in sorted(
            labeled_by("paddlenlp_serving_wasted_tokens_total", "kind").items())},
        "compiles": int(sum(
            labeled_by("paddlenlp_serving_compiles_total", "program").values())),
        "compile_seconds": round(sum(
            labeled_by("paddlenlp_serving_compile_seconds_total", "program").values()), 3),
        "step_gap_p99_ms": round(
            quantile_max("paddlenlp_serving_step_gap_seconds", 0.99) * 1e3, 3),
        "shape_buckets": int(scalar_sum("paddlenlp_serving_jit_shape_buckets")),
    }
    if n_adapters:
        hits = sum(r.hits for r in adapter_registries)
        misses = sum(r.misses for r in adapter_registries)
        record["adapter_hit_rate"] = round(hits / max(hits + misses, 1), 4)
        record["adapter_evictions"] = sum(r.evictions for r in adapter_registries)
        record["multi_lora"] = {
            "adapters": n_adapters,
            "pool_slots": adapter_pool_slots,
            "hits": hits,
            "misses": misses,
            "loads": sum(r.loads for r in adapter_registries),
        }
    if tenant_mix:
        # per-tenant ledger straight off the serving counters: every admitted
        # request and every shed, keyed by the tenant label the isolation
        # layer stamps — summed across replicas
        record["tenants"] = {
            "requests": {k: int(v) for k, v in sorted(labeled_by(
                "paddlenlp_serving_requests_total", "tenant").items())},
            "shed": {k: int(v) for k, v in sorted(labeled_by(
                "paddlenlp_serving_requests_shed_total", "tenant").items())},
        }
        # billing view: fold every replica's usage-meter aggregate and
        # cross-check metered useful tokens against the goodput counters —
        # every booked request finished on one engine here, so the match is
        # exact (the chaos-only slack sources never fire in a clean bench)
        from paddlenlp_tpu.observability.usage import merge_aggregates

        usage_servers = fleet.servers if fleet is not None else [server]
        usage_fold = merge_aggregates(
            [s.loop.usage.snapshot() for s in usage_servers])
        ledger_useful = labeled_sum("paddlenlp_serving_useful_tokens_total")
        record["usage"] = {
            "records": usage_fold["records"],
            "reconciliation_ok": usage_fold["totals"]["useful_tokens"]
            == int(ledger_useful),
            "per_tenant_tokens": {
                t: int(b.get("prompt_tokens", 0) - b.get("cached_tokens", 0)
                       + b.get("completion_tokens", 0))
                for t, b in sorted(usage_fold["tenants"].items())},
        }
    # recorder-overhead A/B facts: run once with PDNLP_TPU_FLIGHT_RECORDER=0
    # and once without, diff value/tails — these two fields label the arms
    record["flight_recorder"] = RECORDER.enabled
    record["flight_events"] = len(RECORDER)
    if surge:
        pq = lambda arr, q: (sorted(arr)[min(int(q * len(arr)), len(arr) - 1)]
                             if arr else 0.0)
        pt = surge_stats["phase_ttft"]
        record["surge"] = {
            "rate_from": surge[0], "rate_to": surge[1], "ramp_s": surge[2],
            "requests": n_requests,
            "shed": surge_stats["shed"],
            "shed_best_effort": surge_stats["shed_best_effort"],
            "rejected": surge_stats["rejected"],
            # the replicas' own shed counter (brownout + deadline rejects),
            # covering direct sheds the router re-routed around
            "replica_shed_total": int(
                labeled_sum("paddlenlp_serving_requests_shed_total")),
            "p99_ttft_before_ms": round(pq(pt["before"], 0.99) * 1e3, 1),
            "p99_ttft_during_ms": round(pq(pt["during"], 0.99) * 1e3, 1),
            "p99_ttft_after_ms": round(pq(pt["after"], 0.99) * 1e3, 1),
            "interactive_p99_ttft_ms": round(
                pq(surge_stats["interactive_ttft"], 0.99) * 1e3, 1),
            "slo_trajectory": slo_samples[-20:],
        }
    if scaler is not None:
        ev = list(scaler.events)
        record["autoscale"] = {
            "min": autoscale[0], "max": autoscale[1],
            "scale_ups": sum(1 for _t, a, _d in ev if a == "up"),
            "scale_downs": sum(1 for _t, a, _d in ev if a == "down"),
            "replaces": sum(1 for _t, a, _d in ev if a == "replace"),
            "holds": sum(1 for _t, a, _d in ev if a == "hold"),
            "final_replicas": final_replicas,
            "events": [[round(t - t0, 2), a, d] for t, a, d in ev][-30:],
        }
    if long_mix:
        gaps = sorted(stats["gaps_short"])
        gp = lambda q: gaps[min(int(q * len(gaps)), len(gaps) - 1)] if gaps else 0.0
        record["long_prompt_mix"] = {
            "long_prompts": n_long_issued,
            "long_prompt_tokens": long_tokens,
            "prefill_chunk": prefill_chunk,
            # client-observed tails: the chatty requests' inter-token gaps are
            # the decode stalls the chunked prefill bounds
            "client_p99_inter_token_ms": round(gp(0.99) * 1e3, 1),
            "client_p50_inter_token_ms": round(gp(0.50) * 1e3, 1),
            "prefill_chunks": int(scalar_sum("paddlenlp_serving_prefill_chunks_total")),
            "decode_stall_p99_ms": round(
                quantile_max("paddlenlp_serving_decode_stall_seconds", 0.99) * 1e3, 1),
        }
    if multi_turn:
        # per-turn view of the conversation-lifetime hierarchy: turn 1 is the
        # cold long opener, turns 2..K should hit the (device or host) cache
        # for the whole history — hit rate > 0 with spills > 0 is the proof
        # the HOST tier served turns the device LRU had already evicted
        per_turn = []
        for t, rows in enumerate(turn_rows):
            tt = sorted(r[0] for r in rows)
            cached = sum(r[1] for r in rows)
            prompt = sum(r[2] for r in rows)
            per_turn.append({
                "turn": t + 1,
                "ttft_p50_ms": round(
                    (tt[len(tt) // 2] if tt else 0.0) * 1e3, 1),
                "cache_hit_rate": round(cached / prompt, 4) if prompt else 0.0,
                "cached_tokens": cached,
                "prompt_tokens": prompt,
            })
        mt_promote_bytes = scalar_sum("paddlenlp_serving_kv_host_promote_bytes_total")
        record["multi_turn"] = {
            "turns": multi_turn,
            "conversations": n_convs,
            "ttft_turn1_ms": per_turn[0]["ttft_p50_ms"],
            "ttft_turnk_ms": per_turn[-1]["ttft_p50_ms"],
            "per_turn": per_turn,
            "per_turn_cache_hit_rate": [pt["cache_hit_rate"] for pt in per_turn],
            "host_spills": int(scalar_sum("paddlenlp_serving_kv_host_spills_total")),
            "host_promotes": int(
                scalar_sum("paddlenlp_serving_kv_host_promotes_total")),
            "host_blocks": int(scalar_sum("paddlenlp_serving_kv_host_blocks")),
            "promote_bytes": int(mt_promote_bytes),
            "promote_bandwidth_mb_s": round(mt_promote_bytes / dt / 1e6, 3),
        }
    if disagg:
        # per-stage view: TTFT is prefill-stage latency, the chatty client
        # inter-token tail is decode-stage latency, and the migration series
        # is the traffic between them
        def stage_gauge(name, stage):
            total = 0.0
            for f in replica_fams:
                fam = f.get(name)
                if fam is None:
                    continue
                for (_sample, labels), v in fam.samples.items():
                    if dict(labels).get("stage") == stage:
                        total += v
            return total / max(len(replica_fams), 1)

        dgaps = sorted(stats["gaps_short"])
        dgp = lambda q: dgaps[min(int(q * len(dgaps)), len(dgaps) - 1)] if dgaps else 0.0
        record["disagg"] = {
            "stages": f"{disagg[0]},{disagg[1]}",
            "prefill_stage": {
                "ttft_p50_ms": round(p(0.50) * 1e3, 1),
                "ttft_p99_ms": round(p(0.99) * 1e3, 1),
                "kv_utilization": round(
                    stage_gauge("paddlenlp_serving_stage_kv_utilization", "prefill"), 4),
            },
            "decode_stage": {
                "client_p50_inter_token_ms": round(dgp(0.50) * 1e3, 1),
                "client_p99_inter_token_ms": round(dgp(0.99) * 1e3, 1),
                "kv_utilization": round(
                    stage_gauge("paddlenlp_serving_stage_kv_utilization", "decode"), 4),
            },
            "migrations": int(scalar_sum("paddlenlp_serving_kv_migrations_total")),
            "migrated_blocks": int(
                scalar_sum("paddlenlp_serving_kv_migrated_blocks_total")),
            "migrated_bytes": int(
                scalar_sum("paddlenlp_serving_kv_migrated_bytes_total")),
        }
    if fleet is not None:
        router_fams = parse_prometheus_text(scraped)
        share = {}
        req_fam = router_fams.get("paddlenlp_router_requests_total")
        if req_fam is not None:
            for (_sample, labels), v in req_fam.samples.items():
                share[dict(labels).get("replica", "?")] = \
                    share.get(dict(labels).get("replica", "?"), 0.0) + v
        rscalar = lambda name: (router_fams[name].value() or 0.0) if name in router_fams else 0.0
        record["request_share"] = {k: int(v) for k, v in sorted(share.items())}
        record["failovers"] = int(rscalar("paddlenlp_router_failovers_total"))
        record["rerouted"] = int(rscalar("paddlenlp_router_rerouted_total"))
        # hedges_total is labeled by outcome: fold the fired ones (and capped
        # separately — a capped hedge is latency NOT bought back)
        hedge_fam = router_fams.get("paddlenlp_router_hedges_total")
        hedge_by = {}
        if hedge_fam is not None:
            for (_sample, labels), v in hedge_fam.samples.items():
                hedge_by[dict(labels).get("outcome", "?")] = int(v)
        record["hedges"] = sum(v for k, v in hedge_by.items() if k != "capped")
        if hedge_by.get("capped"):
            record["hedges_capped"] = hedge_by["capped"]
        if drain_mid_run:
            record["drained_ok"] = bool(drain_result.get("drained_ok"))
            if "drain_wall_s" in drain_result:
                record["drain_wall_s"] = drain_result["drain_wall_s"]
            if "error" in drain_result:
                record["drain_error"] = drain_result["error"]
        if swap_mid_run:
            # zero-downtime readout: the run already _fail()s on any client
            # error, so streams_lost is the gateable proof that the rollout
            # cost nothing; the in-window p99 isolates the tail the drain/
            # swap/canary walk added on top of steady-state serving
            w0 = rollout_result.get("t0", t0)
            w1 = rollout_result.get("t1", t0 + dt)
            during = sorted(v for at, v in ttft_timed if w0 <= at <= w1)
            d_p99 = (during[min(int(0.99 * len(during)), len(during) - 1)]
                     if during else 0.0)
            record["rollout"] = {
                "status": rollout_result.get("status"),
                "wall_s": rollout_result.get("wall_s"),
                "replicas_swapped": rollout_result.get("replicas_swapped", 0),
                "streams_lost": len(errors),
                "ttft_p99_during_swap_ms": round(d_p99 * 1e3, 1),
            }
        if fleet_slo is not None and fleet_slo.get("windows"):
            # the longest window covers the whole bench run (process lifetime)
            widest = fleet_slo["windows"][max(
                fleet_slo["windows"], key=lambda w: int(w.rstrip("s")))]
            objectives = fleet_slo.get("objectives", {})
            record["fleet_availability"] = round(widest["availability"], 6)
            record["fleet_availability_burn_rate"] = round(
                widest["availability_burn_rate"], 3)
            record["fleet_ttft_burn_rate"] = round(widest["ttft_burn_rate"], 3)
            record["fleet_ttft_violation_rate"] = round(
                widest["ttft_violation_rate"], 4)
            record["ttft_objective_ms"] = round(
                objectives.get("ttft_threshold_s", 0.0) * 1e3, 1)
            record["server_ttft_p99_ms"] = round(
                quantile_max("paddlenlp_serving_ttft_seconds", 0.99) * 1e3, 1)
    print(json.dumps(record))


def main() -> None:
    # subprocess isolation, CPU only (the child is pinned to the CPU platform):
    # a deadlocked loop cannot eat the caller — the watchdog timeout always
    # produces the JSON failure record
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--run", *sys.argv[1:]],
            capture_output=True, text=True, timeout=RUN_TIMEOUT_S,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
    except subprocess.TimeoutExpired:
        _fail(f"serving smoke run timed out after {RUN_TIMEOUT_S}s")
        return
    for line in reversed((proc.stdout or "").strip().splitlines()):
        if line.startswith("{"):
            print(line)
            sys.exit(proc.returncode)
    tail = "\n".join(((proc.stdout or "") + (proc.stderr or "")).strip().splitlines()[-8:])
    _fail(f"serving smoke produced no JSON line (rc={proc.returncode}): {tail}")


if __name__ == "__main__":
    if "--run" in sys.argv:
        try:
            run()
        except Exception as e:
            _fail(f"{type(e).__name__}: {e}")
    else:
        main()
