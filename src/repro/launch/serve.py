"""Serving driver: batched prefill + autoregressive decode with the ETAP
pipeline (the paper's workload). Runs on CPU with reduced configs and on
one TPU at DeepSeek-R1 chip-share widths (chip_smoke.py), where attention
takes the compiled ETAP kernels; production-mesh serving is proven by
dryrun.py.

Two cache layouts:

  paged (default) — continuous batching against the block-pool KV cache
      (runtime/paged_cache.py): ragged-length requests are admitted into
      free batch slots whenever the allocator can reserve their full token
      budget, their prompts run as CHUNKED paged prefill interleaved with
      the decode batch under a per-step token budget (--prefill-chunk /
      --token-budget — admission never stalls in-flight decodes), decode
      steps run the whole ragged batch through the paged ETAP kernels, and
      finished sequences release their blocks so queued requests join
      mid-stream.  Throughput is length-aware: only tokens actually
      generated count.

  dense — the legacy fixed-batch path: one jitted lax.scan over steps, every
      sequence runs every step (useful as the single-request-shape baseline
      and for seq-sharded meshes, which the paged path doesn't cover yet).

Observability (DESIGN.md §15): every stat a run reports is recorded into
one per-run MetricsRegistry (runtime/telemetry.py) and the ``[serve]``
summary renders from its snapshot (launch/obs.py) — ``--metrics-out``
archives the same snapshot as JSON.  The paged loop's spans (one
``serve.tick`` per iteration, a launch / wait split of every device
call, admission, prefix-cache work, GC) go to ``jax.profiler``'s trace on
the device trace's clock whenever a profiler session is open;
``--trace-out`` also keeps them, with the request-lifecycle instants, in
a bounded ring buffer exported as Chrome trace-event JSON.  Telemetry
never touches token streams: telemetry-on output is bitwise identical to
telemetry-off (tests/test_telemetry.py + BENCH_obs.json gate it).

    PYTHONPATH=src python -m repro.launch.serve --arch deepseek_r1_671b \
        --reduced --batch 4 --prompt 64 --gen 32 --mode etap \
        --cache-layout paged --requests 8 --trace-out /tmp/trace.json
"""
from __future__ import annotations

import argparse
import os
import time
from collections import deque
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced
from repro.core import attn_spec
from repro.kernels import softmax_state
from repro.launch import obs
from repro.models import model
from repro.runtime import scheduler, spec_decode, telemetry
from repro.runtime.fault_tolerance import (FailureInjector,
                                           HeartbeatRegistry, WorkerFailure)
from repro.runtime.paged_cache import (KV_LAYOUTS, BlockPool,
                                       layout_for, layout_for_bytes)
from repro.runtime.prefix_cache import PrefixCache


def run_dense(args, cfg) -> dict:
    reg = telemetry.MetricsRegistry()
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng, cfg)
    B, S = args.batch, args.prompt
    max_len = S + args.gen
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    t0 = time.perf_counter()
    logits, cache, pos = model.prefill(params, cfg, {"tokens": tokens}, max_len)
    t_prefill = time.perf_counter() - t0

    # the whole generation is ONE jitted lax.scan over steps (cache donated
    # through the scan carry): decode timing measures the kernels, not
    # per-token Python dispatch / host-device sync overhead.
    spec = attn_spec.AttnSpec(mode=args.mode, kv_splits=args.kv_splits)

    def generate(params, cache, first_tok, pos0):
        def step(carry, i):
            tok, cache = carry
            logits, cache = model.decode_step(params, cfg, cache, tok,
                                              pos0 + i, spec=spec)
            return (jnp.argmax(logits, axis=-1), cache), tok
        (_, cache), toks = jax.lax.scan(
            step, (first_tok, cache), jnp.arange(args.gen, dtype=jnp.int32))
        return jnp.swapaxes(toks, 0, 1), cache            # [B, gen]

    gen_fn = jax.jit(generate, donate_argnums=(1,))
    cur = jnp.argmax(logits, axis=-1)
    pos0 = jnp.asarray(pos, jnp.int32)
    compiled = gen_fn.lower(params, cache, cur, pos0).compile()

    t0 = time.perf_counter()
    gen, cache = compiled(params, cache, cur, pos0)
    jax.block_until_ready(gen)
    t_decode = time.perf_counter() - t0
    # length-aware accounting: the fixed-batch scan really does generate
    # `gen` tokens for every one of the B sequences (no early exit), so
    # tokens served == B * gen here — but it is counted, not assumed, to
    # match the continuous-batching report.
    tokens_served = int(gen.shape[0] * gen.shape[1])
    reg.counter("serve/decode_tokens").inc(tokens_served)
    reg.counter("serve/decode_steps").inc(int(args.gen))
    snap = reg.snapshot()
    if args.metrics_out:
        obs.write_metrics(args.metrics_out, snap,
                          config=f"serve:{args.arch}:dense")
    for line in obs.summarize_dense(snap, {
            "arch": args.arch, "mode": args.mode,
            "rescale": softmax_state.default_mode(),
            "batch": B, "prompt": S, "gen": args.gen,
            "t_prefill": t_prefill, "t_decode": t_decode,
            "metrics_path": args.metrics_out,
            "sample": gen[0][:16].tolist()}):
        obs.emit(line)
    return {"tokens": gen, "t_prefill": t_prefill, "t_decode": t_decode,
            "tokens_served": tokens_served, "metrics": snap}


def _make_requests(args, vocab: int):
    """Ragged request stream: prompt/gen lengths drawn from a few quantized
    buckets (bounds prefill re-tracing) around --prompt/--gen.

    ``--shared-prefix N`` makes every prompt start with the SAME N tokens
    (a shared system prompt) followed by a per-request random tail — the
    prefix-cache workload.  The stream is identical for a given seed
    whether or not the prefix cache is enabled (the flag only changes how
    it is served), which is what makes the on/off bitwise-equivalence
    check meaningful.

    Multi-tenant knobs (DESIGN.md §12) ride on SEPARATE rng streams so
    enabling them never perturbs the prompt/length draws — the same seed
    serves the same tokens contended or uncontended, which is what makes
    the preempted-vs-uncontended bitwise check meaningful:
      · --priority-classes N draws a class in [0, N) per request
        (0 = most important);
      · --arrival-rate R staggers arrivals over scheduler ticks —
        Poisson inter-arrival gaps (--trace uniform) or adversarial
        over-admission bursts of --burst-size simultaneous requests
        (--trace burst)."""
    rng = np.random.default_rng(args.seed + 1)
    # buckets never exceed --prompt: the pool layout is sized for
    # prompt + gen, so every request must fit it by construction
    p_buckets = sorted({max(1, args.prompt // 2), max(1, 3 * args.prompt // 4),
                        args.prompt})
    g_buckets = sorted({max(1, args.gen // 2), args.gen})
    shared = None
    if args.shared_prefix:
        assert args.shared_prefix < args.prompt, \
            "--shared-prefix must leave room for a per-request tail"
        shared = rng.integers(0, vocab, size=(args.shared_prefix,))
    n = args.requests
    prios = [0] * n
    if getattr(args, "priority_classes", 1) > 1:
        prng = np.random.default_rng(args.seed + 2)
        prios = prng.integers(0, args.priority_classes, size=n).tolist()
    arrivals = [0] * n
    if getattr(args, "arrival_rate", 0.0) > 0:
        arng = np.random.default_rng(args.seed + 3)
        if args.trace == "burst":
            bsz = max(1, args.burst_size)
            gap = max(1, round(bsz / args.arrival_rate))
            arrivals = [(i // bsz) * gap for i in range(n)]
        else:
            gaps = arng.exponential(1.0 / args.arrival_rate, size=n)
            arrivals = np.floor(np.cumsum(gaps)).astype(int).tolist()
    reqs = []
    for i in range(n):
        plen = int(rng.choice(p_buckets))
        glen = int(rng.choice(g_buckets))
        if shared is None:
            toks = rng.integers(0, vocab, size=(plen,))
        else:
            plen = max(plen, args.shared_prefix + 1)
            tail = rng.integers(0, vocab, size=(plen - args.shared_prefix,))
            toks = np.concatenate([shared, tail])
        reqs.append({"id": i, "prompt": jnp.asarray(toks, jnp.int32),
                     "gen": glen, "priority": int(prios[i]),
                     "arrival": int(arrivals[i])})
    return reqs


def _dsa_rows(start: int, count: int, topk: int) -> tuple:
    """(rows scored, rows selected) of ``count`` query rows at positions
    start .. start + count - 1 under DeepSeek Sparse Attention: row i
    scores its start + i + 1 causal rows and keeps min(topk, that)."""
    scored = count * start + count * (count + 1) // 2
    lo, hi = start + 1, start + count            # causal rows of the rows
    full = max(0, hi - max(lo, topk + 1) + 1)    # rows that keep topk
    part = min(hi, topk) - lo + 1 if lo <= topk else 0
    selected = full * topk + (part * (lo + min(hi, topk)) // 2
                              if part > 0 else 0)
    return scored, selected


def paged_programs(cfg, layout, spec):
    """Every jitted program that takes or returns the paged pool.

    ``init`` creates the pool; ``step`` / ``prefill`` / ``verify`` (the
    serve entries), ``copy_block`` (COW) and ``write_blocks`` (swap
    restore) each take the pool donated and hand it back in the layout it
    came in, so it is updated in place from one program to the next.  A
    new program that rebinds the pool must do the same (DESIGN.md §8).
    Named functions, so the compiled modules and the device trace say
    which program ran.  Under DeepSeek Sparse Attention ``step`` and
    ``prefill`` also return the rows each layer selected (the decode
    step's positions, the chunk's last row's mask)."""
    dsa = cfg.mla is not None and cfg.mla.indexer is not None

    def serve_init_pool():
        return model.init_paged_cache(cfg, layout, kv_dtype=spec.kv_dtype)

    def serve_decode_step(p, c, t, table, lengths):
        return model.decode_step(p, cfg, c, t, None, spec=spec,
                                 cache_layout="paged", block_table=table,
                                 lengths=lengths, with_selection=dsa)

    def serve_prefill_chunk(p, c, t, table, lengths):
        return model.prefill_chunk(p, cfg, c, t, table, lengths, spec=spec,
                                   with_selection=dsa)

    def serve_verify_step(p, c, t, table, lengths):
        return model.verify_step(p, cfg, c, t, table, lengths, spec=spec)

    return SimpleNamespace(
        init=jax.jit(serve_init_pool),
        step=jax.jit(serve_decode_step, donate_argnums=(1,)),
        prefill=jax.jit(serve_prefill_chunk, donate_argnums=(1,)),
        verify=jax.jit(serve_verify_step, donate_argnums=(1,)),
        copy_block=jax.jit(model.copy_paged_block, donate_argnums=(0,)),
        write_blocks=jax.jit(model.write_paged_blocks, donate_argnums=(0,)))


def run_paged(args, cfg, *, record_logits: bool = False) -> dict:
    """Continuous-batching serve loop: CHUNKED paged prefill interleaved
    with decode under a per-step token budget, driven by the SLO-aware
    scheduler (runtime/scheduler.py, DESIGN.md §9/§12).

    Per tick:
      (0) requests whose arrival tick has come join the scheduler queue;
          the optional ``--paranoia N`` sweep runs the pool's full
          conservation + table audit.
      (1) the scheduler places candidates in (priority, PREEMPTED-first,
          arrival, id) order: cache-aware admission when the prefix cache
          is on (the radix tree is walked, the matched block-aligned
          prefix maps by refcount bump, LRU trie-only leaves are evicted
          under pressure — DESIGN.md §10), swap-tier restore for
          preempted-by-swap requests, and PREEMPTION of strictly-lower-
          priority victims when placement refuses (--preemption swap
          evacuates the victim's blocks to host RAM; recompute drops them
          and re-prefills at restore).  A candidate refused even after
          preemption backs off (--retry-backoff) — never a permanent
          refusal.  Admission reserves blocks only; no prompt tokens run.
      (2) spend the step's token budget (``--token-budget``): the decode
          batch first, then prefill chunks of ``--prefill-chunk`` tokens
          from cold slots FCFS while they fit the remainder — a long
          prompt never head-of-line-blocks in-flight decodes (chunked-
          prefill continuous batching).  Under an ITL SLO the scheduler
          shrinks the prefill SHARE of the budget (chunk shapes never
          change — outputs stay bitwise).  The first chunk after a cache
          hit or restore is trimmed onto the GLOBAL chunk grid, so every
          later chunk has exactly the shape the uncached run would have
          used — cached/restored decode is BITWISE identical to the
          uncontended run, not merely close (DESIGN.md §10/§12).  A
          request finishing its prompt inserts its full prompt blocks
          into the trie right away; a RESTORED recompute victim re-seeds
          from the same prefill logits and then TEACHER-FORCES its
          already-delivered tokens through the decode kernel (replay —
          delivered exactly once, re-fed as needed).
      (3) one jitted paged decode step over the decoding slots (cold
          slots' table rows are masked to the null block), then retire
          finished sequences.  ``--fault-rate`` injects deterministic
          worker failures here: the step is discarded, the victim slot is
          requeued through the recompute path, and the heartbeat registry
          notices the missed beat — greedy outputs stay bitwise-identical
          to the unfailed run.

    Re-tracing is bounded: prefill_chunk compiles once per distinct chunk
    size, and chunk sizes are min(--prefill-chunk, remaining prompt) over
    the quantized prompt buckets of :func:`_make_requests`.

    ``record_logits=True`` fills the returned ``seed_logits`` and
    ``first_decode_logits`` ({request id: fp32 logits row}) and, under
    DeepSeek Sparse Attention, ``dsa_selected``: each request's prompt and
    the positions every layer selected for its last prefill row and each
    decode row; otherwise all stay empty and nothing more leaves the
    device."""
    indexer = cfg.mla.indexer if cfg.mla is not None else None
    if indexer is not None and (args.kv_dtype != "fp"
                                or args.spec_tokens > 0):
        # the quantized pools and the verify pass carry no index keys
        raise ValueError(
            "DeepSeek Sparse Attention (an indexer config) serves bf16 pools "
            "without speculation: --kv-dtype must be fp and --spec-tokens 0 "
            f"(got --kv-dtype {args.kv_dtype}, --spec-tokens "
            f"{args.spec_tokens})")
    # one fresh registry per run — back-to-back runs in one process
    # (tests, benchmarks) must never mix counters.  Every subsystem below
    # (pool, scheduler, heartbeats, injector, drafter) writes into it.
    reg = telemetry.MetricsRegistry()
    # spans always go to the profiler (a no-op without a session); the
    # Chrome-JSON ring only under --trace-out
    tracer = telemetry.Tracer(
        capacity=args.trace_buffer if args.trace_out else 0)
    params = model.init(jax.random.PRNGKey(args.seed), cfg)
    B = args.batch
    max_total = args.prompt + args.gen
    layout = layout_for(B, max_total, block_size=args.page_size,
                        spare_blocks=args.spare_blocks)
    if args.kv_dtype != "fp":
        # capacity accounting (DESIGN.md §11): hold the POOL BYTE BUDGET
        # fixed at what the fp layout would have spent for --batch slots
        # and let the cheaper quantized rows buy more blocks — and with
        # them more concurrent batch slots (~2x at int8 for bf16 configs).
        fp_bytes = model.paged_row_bytes(cfg, "fp")
        q_bytes = model.paged_row_bytes(cfg, args.kv_dtype)
        budget = (layout.num_blocks - 1) * layout.block_size * fp_bytes
        layout, B = layout_for_bytes(budget, q_bytes, max_total,
                                     block_size=args.page_size,
                                     spare_blocks=args.spare_blocks)
    host_blocks = args.host_blocks
    if args.preemption == "swap" and host_blocks == 0:
        host_blocks = layout.num_blocks - 1   # host tier mirrors the pool
    bp = BlockPool(layout, B, host_blocks=host_blocks, metrics=reg)
    prefix = (PrefixCache(layout.block_size, tracer=tracer)
              if args.prefix_cache else None)
    # serve spec: mode / kv_splits / kv layout / speculation for every
    # attention layer of every program
    spec = attn_spec.AttnSpec(mode=args.mode, kv_splits=args.kv_splits,
                              kv_dtype=args.kv_dtype,
                              spec_tokens=args.spec_tokens,
                              spec_draft=args.spec_draft)
    progs = paged_programs(cfg, layout, spec)
    cache = progs.init()
    # whether the device keeps every pool leaf row-major, the layout the
    # kernels read: 0 means each program relays the pool (DESIGN.md §8)
    reg.gauge("paged/pool_row_major").set(int(all(
        a.format.layout.major_to_minor == tuple(range(a.ndim))
        for a in jax.tree.leaves(cache))))
    pending = deque(sorted(_make_requests(args, cfg.vocab_size),
                           key=lambda r: (r["arrival"], r["id"])))
    n_requests = len(pending)
    chunk = max(1, args.prefill_chunk)
    # auto budget: the whole decode batch plus one prefill chunk per step
    budget = args.token_budget if args.token_budget > 0 else B + chunk

    # KVOps: the scheduler stays device-free; these closures move swap/COW
    # bytes through the LIVE cache pytree (holder — the jitted entries
    # donate and rebind it, so the closures must not capture a stale ref)
    holder = {"cache": cache}

    def _kv_read(ids):
        return model.read_paged_blocks(holder["cache"], ids)

    def _kv_write(ids, rows, start):
        sel = jax.tree.map(lambda r: r[:, start:start + len(ids)], rows)
        holder["cache"] = progs.write_blocks(holder["cache"],
                                             np.asarray(ids, np.int32), sel)

    def _kv_copy(src, dst):
        holder["cache"] = progs.copy_block(holder["cache"], src, dst)

    sched = scheduler.Scheduler(
        bp, prefix,
        scheduler.KVOps(_kv_read, _kv_write, _kv_copy),
        scheduler.SchedulerConfig(
            preemption=args.preemption, slo_ttft_ms=args.slo_ttft,
            slo_itl_ms=args.slo_itl,
            backoff_cap=max(1, args.retry_backoff)),
        metrics=reg, tracer=tracer)
    injector = (FailureInjector.from_rate(args.fault_rate, metrics=reg)
                if args.fault_rate > 0 else None)
    tick_box = [0]
    # heartbeats on the TICK clock: a beat every tick is alive (gap 1 <=
    # 1.5); the skipped beat of a failure tick (gap 2) trips dead()
    hb = HeartbeatRegistry(timeout_s=1.5, clock=lambda: float(tick_box[0]),
                           metrics=reg)
    WORKER = "decode-worker-0"

    # the cache pytree is DONATED through every jitted entry (as the dense
    # path donates through its scan carry): the pool is updated in place
    # instead of copied per call, keeping admission's peak extra memory at
    # one chunk, not a second pool (paged_programs).
    step_fn, prefill_fn = progs.step, progs.prefill
    # warm the decode step OUTSIDE the timed region (the dense path also
    # compiles before its timer): all slots inactive → the dummy rows land
    # in the reserved null block, so rebinding the returned cache (the
    # donated input is gone) leaves every real pool row untouched.
    table0, lengths0 = bp.device_views()
    logits0, holder["cache"] = step_fn(params, holder["cache"],
                                       jnp.zeros((B,), jnp.int32),
                                       table0, lengths0)[:2]
    jax.block_until_ready(logits0)

    # speculative decode (DESIGN.md §14): a host-side drafter proposes
    # k-1 tokens per eligible slot and ONE prefill-shaped verify launch
    # scores all k positions; greedy acceptance keeps the delivered stream
    # bitwise identical to one-at-a-time decode.
    k_max = args.spec_tokens
    verify_fn = drafter = None
    if k_max > 0:
        drafter = spec_decode.make_drafter(args.spec_draft, params,
                                           metrics=reg)
        verify_fn = progs.verify
        # warm the verify pass outside the timer too, with the same all-
        # null masked launch as step_fn: the k dummy rows land in the null
        # block and compile time never lands in t_decode
        logits0, holder["cache"] = verify_fn(params, holder["cache"],
                                             jnp.zeros((B, k_max), jnp.int32),
                                             table0, lengths0)
        jax.block_until_ready(logits0)

    # hot-loop instrument handles: one attribute write per event, no
    # registry lookup inside the tick loop
    c_tokens = reg.counter("serve/decode_tokens")
    c_steps = reg.counter("serve/decode_steps")
    c_spec_steps = reg.counter("serve/spec_verify_steps")
    c_spec_prop = reg.counter("serve/spec_proposed")
    c_spec_acc = reg.counter("serve/spec_accepted")
    c_chunks = reg.counter("serve/prefill_chunks")
    c_inter = reg.counter("serve/interleaved_steps")
    c_pf = reg.counter("serve/prefill_tokens")
    c_replay = reg.counter("serve/replayed_tokens")
    c_restarts = reg.counter("serve/worker_restarts")
    c_ticks = reg.counter("serve/ticks")
    # DeepSeek Sparse Attention: rows the indexer scored and rows attention
    # kept, per query row summed over the run (each layer does the same)
    c_scored = c_selected = None
    if indexer is not None:
        c_scored = reg.counter("dsa/rows_scored")
        c_selected = reg.counter("dsa/rows_selected")
    g_queued = reg.gauge("sched/queued")
    g_running = reg.gauge("sched/running")
    t_prefill = 0.0
    # record_logits: each request's last prefill logits and first decode
    # step's logits, both computed from inputs two attention paths share —
    # what a comparison of the paths holds to its bound (chip_smoke.py)
    seed_logits, first_logits = {}, {}
    # record_logits under DeepSeek Sparse Attention: the positions every
    # layer selected for each request's last prefill row and every decode
    # row ({request id: {"prompt", "rows": {position: [n_layers, n]}}}),
    # what a reference needs to tell a selection fault from a near-tie
    dsa_selected = {}
    sel_dtype = (np.uint16 if bp.table.shape[1] * layout.block_size <= 2 ** 16
                 else np.int32)

    def keep_selection(r, pos, rows):
        dsa_selected.setdefault(r.id, {"rows": {}})["rows"][pos] = \
            rows.astype(sel_dtype)

    # spans (DESIGN.md §15): one serve.tick per iteration; inside it each
    # device launch is a *.launch span (host prep + dispatch) and a *.wait
    # span (the host blocked on the device); the rest is host work
    t0 = time.perf_counter()
    with tracer.gc_spans():
        while pending or sched.queue or sched.by_slot:
            tick = tick_box[0]
            with tracer.span("serve.tick", step_num=tick):
                now = time.perf_counter()
                # ---- (0) arrivals + paranoia sweep + heartbeat bookkeeping
                while pending and pending[0]["arrival"] <= tick:
                    req = pending.popleft()
                    sched.add(scheduler.Request(
                        id=req["id"], prompt=req["prompt"], gen=req["gen"],
                        priority=req["priority"], arrival=req["arrival"]),
                        now)
                if args.paranoia and tick % args.paranoia == 0:
                    bp.audit()
                if hb.dead():                 # missed beat = failure tick
                    c_restarts.inc()          # ...worker comes back below
                    tracer.instant("worker_restart", args={"tick": tick})

                # ---- (1) admission / restore / preemption (scheduler
                # policy)
                with tracer.span("sched.admit"):
                    sched.admit(tick, now)

                running = sched.running()
                dec = [r for r in running if r.decoding]
                # speculation is restricted to slots with at least k_max
                # deliveries left (uniform-k launches: start + k_max never
                # exceeds the slot's reserved budget exactly when
                # remaining >= k_max) that are not teacher-forcing a
                # restore replay; everything else takes the plain
                # one-token step below
                spec_dec = [r for r in dec if k_max > 0 and not r.replay
                            and r.remaining >= k_max]
                spec_slots = {r.slot for r in spec_dec}
                # decode tokens this step (each spec slot runs k_max
                # verify rows)
                spent = len(dec) + max(0, k_max - 1) * len(spec_dec)
                # ITL SLO: shrink the prefill share of the budget when
                # delivered inter-token latency runs hot (no-op at the
                # default budget split)
                budget_eff = spent + sched.prefill_quota(
                    max(0, budget - spent))

                # ---- (2) prefill chunks from cold slots under the budget
                pf_tokens = 0
                cold = sorted((r for r in running if not r.decoding),
                              key=lambda r: r.admit_seq)
                for r in cold:
                    b = r.slot
                    plen = r.plen
                    # trim the first tail chunk onto the global chunk
                    # grid: after a prefix-cache hit (or a restore) at a
                    # non-chunk-multiple offset, the next chunk ends at
                    # the grid point, so every later chunk has the exact
                    # shape the uncached run would have used (bitwise-
                    # equal decode, DESIGN.md §10).  Uncached (pf_pos %
                    # chunk == 0) this is the plain min(chunk, remaining).
                    c = min(chunk - r.pf_pos % chunk, plen - r.pf_pos)
                    if spent + c > budget_eff and spent > 0:
                        break                 # budget spent — defer chunk
                    done = r.pf_pos + c == plen
                    tp = time.perf_counter()
                    with tracer.span("serve.prefill.launch", req=r.id,
                                     tokens=c):
                        toks_c = r.prompt[None, r.pf_pos:r.pf_pos + c]
                        trow = jnp.array(bp.table[b:b + 1])
                        lrow = jnp.array(bp.lengths[b:b + 1])
                        sched.start_prefill(r, time.perf_counter())
                        out = prefill_fn(params, holder["cache"], toks_c,
                                         trow, lrow)
                        logits, holder["cache"] = out[:2]
                    with tracer.span("serve.prefill.wait", req=r.id):
                        jax.block_until_ready(logits)
                        t_prefill += time.perf_counter() - tp
                        if done:
                            seed = int(jnp.argmax(logits[0, -1]))
                            if record_logits:
                                seed_logits.setdefault(
                                    r.id,
                                    np.asarray(logits[0, -1], np.float32))
                            if record_logits and indexer is not None:
                                mask = np.asarray(out[2])[:, 0]
                                keep_selection(r, plen - 1, np.stack(
                                    [np.flatnonzero(m) for m in mask]))
                    if c_scored is not None:
                        sc, se = _dsa_rows(int(bp.lengths[b]), c,
                                           indexer.topk)
                        c_scored.inc(sc)
                        c_selected.inc(se)
                    bp.extend(b, c)
                    r.pf_pos += c
                    spent += c
                    pf_tokens += c
                    c_pf.inc(c)
                    c_chunks.inc()
                    if done:                  # prompt done -> start decoding
                        if r.replay:
                            # restored victim: the re-prefill must
                            # re-derive the first delivered token
                            # bit-for-bit (grid invariant)
                            assert seed == r.replay[0], \
                                f"request {r.id}: restore diverged at " \
                                f"prefill (got {seed}, delivered " \
                                f"{r.replay[0]})"
                        else:
                            r.cur = seed
                        r.decoding = True
                        if prefix is not None:
                            # cache the prompt's full blocks NOW (not at
                            # release): queued requests share them while
                            # this one decodes
                            prefix.insert(np.asarray(r.prompt),
                                          bp.block_ids(b), bp)

                # ---- (3) one ragged decode step over the decoding slots
                if dec:
                    if injector is not None:
                        try:
                            injector.check(tick)
                        except WorkerFailure:
                            # the decode worker died mid-step: its outputs
                            # never land — requeue the victim through the
                            # recompute path and skip the step (no beat →
                            # dead() next tick)
                            victim = max(dec, key=lambda r: r.slot)
                            sched.fail_running(victim.slot, tick)
                            tick_box[0] += 1
                            continue
                    # mask cold slots (and, for each launch, the OTHER
                    # launch's slots) to the null block: the decode write
                    # for them must not land inside a half-prefilled
                    # prompt or a live sequence
                    plain = [r for r in dec if r.slot not in spec_slots]
                    if plain:
                        with tracer.span("serve.decode.launch",
                                         slots=len(plain)):
                            plain_slots = {r.slot for r in plain}
                            table_m = bp.table.copy()
                            lens_m = bp.lengths.copy()
                            cur_arr = np.zeros((B,), np.int64)
                            for b in range(B):
                                if b not in plain_slots:
                                    table_m[b] = 0
                                    lens_m[b] = 0
                            for r in plain:
                                cur_arr[r.slot] = (r.replay[0] if r.replay
                                                   else r.cur)
                            out = step_fn(
                                params, holder["cache"],
                                jnp.array(cur_arr, jnp.int32),
                                jnp.array(table_m), jnp.array(lens_m))
                            logits, holder["cache"] = out[:2]
                        with tracer.span("serve.decode.wait"):
                            nxt = np.asarray(jnp.argmax(logits, axis=-1))
                            new = ([r for r in plain
                                    if r.id not in first_logits]
                                   if record_logits else [])
                            if new:
                                rows = np.asarray(logits, np.float32)
                                first_logits.update({r.id: rows[r.slot]
                                                     for r in new})
                            if record_logits and indexer is not None:
                                idx = np.asarray(out[2])     # [L, B, k]
                                for r in plain:
                                    pos = int(lens_m[r.slot])
                                    n = min(indexer.topk, pos + 1)
                                    keep_selection(r, pos, idx[:, r.slot, :n])
                        c_steps.inc()
                        if pf_tokens:
                            c_inter.inc()
                        if c_scored is not None:
                            for r in plain:
                                sc, se = _dsa_rows(int(bp.lengths[r.slot]), 1,
                                                   indexer.topk)
                                c_scored.inc(sc)
                                c_selected.inc(se)

                        # ---- retire / bookkeep (host side)
                        with tracer.span("serve.decode.retire"):
                            now = time.perf_counter()
                            for r in plain:
                                b = r.slot
                                if r.replay:
                                    # teacher-forced replay: the token was
                                    # already delivered before preemption
                                    # — rebuild its KV row and assert the
                                    # decode path re-derives the NEXT
                                    # token bit-for-bit (the bitwise-
                                    # restore guarantee made falsifiable
                                    # at every replayed position)
                                    fed = r.replay.popleft()
                                    bp.append(b)
                                    expect = (r.replay[0] if r.replay
                                              else r.cur)
                                    assert int(nxt[b]) == int(expect), \
                                        f"request {r.id}: replay " \
                                        f"diverged after token {fed} " \
                                        f"(got {int(nxt[b])}, expected " \
                                        f"{int(expect)})"
                                    c_replay.inc()
                                else:
                                    sched.deliver(r, r.cur, now)
                                    c_tokens.inc()
                                    bp.append(b)
                                    r.cur = int(nxt[b])
                                    if r.remaining == 0:
                                        sched.finish(r)

                    if spec_dec:
                        # ---- speculative verify (DESIGN.md §14): draft
                        # k-1 tokens per slot from the committed stream,
                        # score [cur, d_1, .., d_{k-1}] in ONE prefill-
                        # shaped launch against the paged pool, accept the
                        # longest draft prefix matching the model's own
                        # argmax chain.  Greedy acceptance makes the
                        # delivered stream bitwise identical to one-at-a-
                        # time decode whatever the drafter proposes.
                        with tracer.span("serve.verify.launch",
                                         slots=len(spec_dec), k=k_max):
                            table_m = bp.table.copy()
                            lens_m = bp.lengths.copy()
                            tok_arr = np.zeros((B, k_max), np.int64)
                            drafts_by_slot = {}
                            for b in range(B):
                                if b not in spec_slots:
                                    table_m[b] = 0
                                    lens_m[b] = 0
                            for r in spec_dec:
                                b = r.slot
                                history = np.concatenate([
                                    np.asarray(r.prompt),
                                    np.asarray(r.out + [r.cur], np.int64)])
                                ds = (list(drafter(history, k_max - 1))
                                      if k_max > 1 else [])
                                drafts_by_slot[b] = ds
                                tok_arr[b] = [r.cur] + ds
                            logits, holder["cache"] = verify_fn(
                                params, holder["cache"],
                                jnp.array(tok_arr, jnp.int32),
                                jnp.array(table_m), jnp.array(lens_m))
                        with tracer.span("serve.verify.wait"):
                            preds = np.asarray(jnp.argmax(logits, axis=-1))
                        c_steps.inc()
                        c_spec_steps.inc()
                        if pf_tokens:
                            c_inter.inc()

                        # ---- commit / rewind / deliver (host side)
                        with tracer.span("serve.decode.retire"):
                            now = time.perf_counter()
                            for r in spec_dec:
                                b = r.slot
                                start = int(bp.lengths[b])
                                # the verify pass appended k_max KV rows
                                # on device; commit them on the host, then
                                # rewind the rejected tail IN PLACE
                                # (free_blocks=False — the slot keeps its
                                # full reservation, and the garbage rows
                                # sit past the committed length where no
                                # mask ever reads them until the next
                                # launch overwrites them)
                                bp.extend(b, k_max)
                                ds = drafts_by_slot[b]
                                accepted, nxt_tok = \
                                    spec_decode.accept_greedy(ds, preds[b])
                                bp.truncate(b, start + 1 + accepted,
                                            free_blocks=False)
                                for t in [r.cur] + ds[:accepted]:
                                    sched.deliver(r, int(t), now)
                                    c_tokens.inc()
                                c_spec_prop.inc(len(ds))
                                c_spec_acc.inc(accepted)
                                r.cur = int(nxt_tok)
                                if r.remaining == 0:
                                    sched.finish(r)
                # per-tick occupancy gauges: pure reads of pool/scheduler
                # state
                c_ticks.inc()
                bp.observe(reg)
                g_queued.set(len(sched.queue))
                g_running.set(len(sched.by_slot))
                hb.beat(WORKER)
                tick_box[0] += 1
    t_total = time.perf_counter() - t0
    t_decode = t_total - t_prefill

    outputs = {rid: r.out for rid, r in sorted(sched.done.items())}
    for rid, rec in dsa_selected.items():
        rec["prompt"] = np.asarray(sched.done[rid].prompt, np.int32)
    refused_ids = sched.refused_ids
    prefill_tokens_saved = sched.prefill_tokens_saved
    sstats = sched.stats()
    pstats = prefix.stats() if prefix is not None else None
    snap = reg.snapshot()
    trace_stats = (obs.write_trace(tracer, args.trace_out)
                   if args.trace_out else None)
    if args.metrics_out:
        obs.write_metrics(
            args.metrics_out, snap,
            config=f"serve:{args.arch}:paged:{args.kv_dtype}")
    tokens_served = c_tokens.value
    first = outputs[0][:16] if outputs.get(0) else []
    for line in obs.summarize_paged(snap, {
            "arch": args.arch, "mode": args.mode, "batch_slots": B,
            "n_requests": n_requests, "page_size": layout.block_size,
            "pool_blocks": layout.num_blocks - 1,
            "host_blocks": host_blocks, "chunk": chunk, "budget": budget,
            "kv_dtype": args.kv_dtype,
            "rescale": softmax_state.default_mode(),
            "prefix": pstats, "preemption": args.preemption,
            "spec_tokens": k_max, "spec_draft": args.spec_draft,
            "t_prefill": t_prefill, "t_decode": t_decode,
            "refusals": len(refused_ids),
            "prefill_tokens_saved": prefill_tokens_saved,
            "sched": sstats, "classes": sched.class_stats(),
            "trace_stats": trace_stats, "metrics_path": args.metrics_out,
            "sample": first}):
        obs.emit(line)
    return {"outputs": outputs, "tokens_served": tokens_served,
            "batch_slots": B, "kv_dtype": args.kv_dtype,
            "pool_blocks": layout.num_blocks - 1,
            "host_blocks": host_blocks,
            "steps": c_steps.value, "refusals": len(refused_ids),
            "prefill_chunks": c_chunks.value,
            "interleaved_steps": c_inter.value,
            "prefill_tokens": c_pf.value,
            "decode_tokens": tokens_served,
            "prefill_tokens_saved": prefill_tokens_saved,
            "replayed_tokens": c_replay.value,
            "worker_restarts": c_restarts.value,
            "prefix": pstats, "sched": sstats,
            "classes": sched.class_stats(),
            "spec": ({"k": k_max, "draft": args.spec_draft,
                      "steps": c_spec_steps.value,
                      "proposed": c_spec_prop.value,
                      "accepted": c_spec_acc.value,
                      "acceptance_rate":
                          c_spec_acc.value / max(c_spec_prop.value, 1)}
                     if k_max > 0 else None),
            "metrics": snap,
            "seed_logits": seed_logits,
            "first_decode_logits": first_logits,
            "dsa_selected": dsa_selected,
            "t_prefill": t_prefill, "t_decode": t_decode}


def run(args) -> dict:
    # pin the process-wide rescale mode BEFORE any tracing so every kernel
    # entry resolves the same mode (jit_with_rescale keys the cache on it)
    softmax_state.set_default_mode(getattr(args, "rescale",
                                           softmax_state.default_mode()))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.cache_layout == "dense":
        return run_dense(args, cfg)
    return run_paged(args, cfg)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek_r1_671b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4,
                    help="batch slots (paged) / batch size (dense)")
    ap.add_argument("--prompt", type=int, default=64,
                    help="max prompt length (paged draws ragged lengths)")
    ap.add_argument("--gen", type=int, default=32,
                    help="max tokens to generate per request")
    ap.add_argument("--mode", default="etap", choices=["etap", "standard"])
    ap.add_argument("--cache-layout", default="paged",
                    choices=["dense", "paged"],
                    help="KV cache layout; paged = continuous batching "
                         "(the serving default), dense = fixed-batch scan")
    ap.add_argument("--requests", type=int, default=8,
                    help="ragged request count for the paged serve loop")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV block (FlashMLA uses 64)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens per paged prefill chunk "
                         "(chunked-prefill continuous batching)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-step token budget shared by the decode batch "
                         "and prefill chunks (0 = batch + prefill-chunk)")
    ap.add_argument("--spare-blocks", type=int, default=0,
                    help="extra pool blocks beyond batch*max_blocks")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="radix-tree prefix cache: share KV blocks of "
                         "common prompt prefixes across requests and skip "
                         "their prefill (--no-prefix-cache disables)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of a common prompt prefix shared by every "
                         "generated request (the prefix-cache workload; "
                         "0 = fully independent prompts)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="priority classes drawn per request (0 = most "
                         "important; 1 = single-tenant FCFS, the default)")
    ap.add_argument("--slo-ttft", type=float, default=0.0,
                    help="time-to-first-token budget in ms: a request past "
                         "it jumps every priority class at admission "
                         "(0 = off; ordering only — outputs stay bitwise)")
    ap.add_argument("--slo-itl", type=float, default=0.0,
                    help="inter-token latency budget in ms: over-budget "
                         "delivered ITL shrinks the prefill share of the "
                         "step token budget (0 = off; bounds chunked-"
                         "prefill interference, outputs stay bitwise)")
    ap.add_argument("--preemption", default="recompute",
                    choices=["swap", "recompute"],
                    help="victim evacuation mode (DESIGN.md §12): swap "
                         "copies written blocks to the host tier and back "
                         "(bitwise trivially); recompute drops them and "
                         "re-prefills + replays at restore (bitwise by the "
                         "chunk-grid invariant + teacher forcing)")
    ap.add_argument("--host-blocks", type=int, default=0,
                    help="host-RAM swap tier size in blocks (0 with "
                         "--preemption swap sizes the tier to mirror the "
                         "device pool)")
    ap.add_argument("--retry-backoff", type=int, default=1,
                    help="max backoff in ticks between admission retries "
                         "(exponential from 1; 1 = retry every tick, the "
                         "pre-scheduler behavior)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="injected decode-worker failures per tick "
                         "(deterministic schedule via FailureInjector; "
                         "victims requeue through the recompute path and "
                         "outputs stay bitwise; 0 = off)")
    ap.add_argument("--paranoia", type=int, default=0,
                    help="run the BlockPool conservation + full-row table "
                         "audit every N ticks (0 = off; on in tests/CI "
                         "smoke so invariant corruption surfaces at the "
                         "step that caused it)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="request arrivals per scheduler tick (0 = all "
                         "arrive at tick 0)")
    ap.add_argument("--trace", default="uniform",
                    choices=["uniform", "burst"],
                    help="arrival trace shape under --arrival-rate: "
                         "uniform = Poisson gaps; burst = adversarial "
                         "over-admission bursts of --burst-size requests")
    ap.add_argument("--burst-size", type=int, default=4,
                    help="requests per burst for --trace burst")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative decode window k (DESIGN.md §14): "
                         "draft k-1 tokens per eligible decode slot and "
                         "score all k positions in ONE prefill-shaped "
                         "verify launch; greedy acceptance keeps outputs "
                         "bitwise identical to one-at-a-time decode "
                         "(0 = off; paged layout only)")
    ap.add_argument("--spec-draft", default="ngram",
                    choices=list(spec_decode.DRAFT_KINDS),
                    help="draft proposer for --spec-tokens: ngram = "
                         "longest-suffix match over the committed stream "
                         "(free, strong on repetitive traces); head = "
                         "embedding-similarity self-draft chain (not "
                         "supported on fp8 pools)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the request-lifecycle + engine-span trace "
                         "as Chrome trace-event JSON (open in "
                         "ui.perfetto.dev or chrome://tracing; DESIGN.md "
                         "§15; paged layout only; outputs stay bitwise)")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="trace ring-buffer capacity in events: overflow "
                         "drops the OLDEST events (counted in the export) "
                         "instead of growing — bounded memory under any "
                         "run length")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the full metrics-registry snapshot as "
                         "schema-versioned JSON (counters/gauges/histogram "
                         "tails + git sha and jax version provenance)")
    ap.add_argument("--kv-splits", type=int, default=None,
                    help="split-KV count for decode attention "
                         "(default: auto-scheduled)")
    ap.add_argument("--kv-dtype", default=os.environ.get("REPRO_KV_DTYPE",
                                                         "fp"),
                    choices=list(KV_LAYOUTS),
                    help="paged KV storage layout (DESIGN.md §11): fp = "
                         "config dtype; int8/fp8 store per-row quantized "
                         "codes + (scale, zp) and admit ~2x the sequences "
                         "under the same pool byte budget (env default: "
                         "REPRO_KV_DTYPE — the CI int8 leg's hook)")
    ap.add_argument("--rescale", default=os.environ.get("REPRO_RESCALE",
                                                        "amla"),
                    choices=list(softmax_state.MODES),
                    help="online-softmax rescaling mode (DESIGN.md §13): "
                         "amla = deferred power-of-two bias rescaling "
                         "(exponent-add correction, exact in fp); mul = "
                         "textbook multiply-rescale referee (env default: "
                         "REPRO_RESCALE)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args(argv)
    # flag-combo validation: refuse inconsistent speculation configs with
    # a clear CLI error instead of a deep stack trace mid-serve
    if args.spec_tokens < 0:
        ap.error("--spec-tokens must be >= 0")
    if args.spec_tokens > 0 and args.cache_layout == "dense":
        ap.error("--spec-tokens requires --cache-layout paged: the dense "
                 "scan has no block pool to rewind rejected drafts in")
    if args.spec_tokens > 0 and args.spec_draft == "head" \
            and args.kv_dtype == "fp8":
        ap.error("--spec-draft head is not supported with --kv-dtype fp8; "
                 "use --spec-draft ngram")
    if args.trace_buffer < 1:
        ap.error("--trace-buffer must be >= 1")
    if args.cache_layout == "dense" and args.trace_out:
        ap.error("--trace-out requires --cache-layout paged: the dense "
                 "scan is one opaque jitted launch with no per-request "
                 "lifecycle or per-launch spans to record")
    return args


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    run(parse_args())
