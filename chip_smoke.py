#!/usr/bin/env python3
"""Chip smoke run: the paged ETAP serve path on one TPU at DeepSeek-R1 width.

Drives ``repro.launch.serve.run_paged`` (continuous batching, chunked paged
prefill, prefix cache) in this one process, on the one-chip share of an
8-way tensor-parallel, 32-way expert-parallel DeepSeek-R1 deployment, with
random weights made from a seed.  Two phases:

  (a) main serve path: 16 slots, 24 requests, prompts of up to 2048 tokens
      that share a 512-token prefix, up to 64 generated tokens each, page 64,
      chunk 64.  Run once on the Pallas ETAP kernels and once on the XLA
      twins, same seed; greedy tokens and the first decode step's logits
      are compared against the bound stated below.
  (b) split-KV path: 3 requests in turn at batch 1, each with a context of
      16K tokens or more, so the auto plan picks n_splits > 1 and the
      phase-1 partial and combine kernels run; kernel vs XLA again.

Exits non-zero, and prints no result, when JAX finds no TPU, when a phase
raises, or when a comparison misses its bound.  The last line of a run
that passes is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Logit bound, kernel path vs XLA path on the first decode step.  In fp32
# the two paths agree bitwise; in bf16 they differ by rounding (the kernels
# keep P in fp32 for the PV product, the XLA twins round it to bf16),
# carried through 4 layers of a bf16 residual stream — a few percent of
# the largest logit at most.  A kernel that reads a wrong block, mask or
# tile moves logits by the order of the largest logit itself.
LOGIT_RTOL = 5e-2

# The cuts that make DeepSeek-R1 the one-chip share of an 8-way TP /
# 32-way EP deployment (widths, MLA ranks, expert width, top_k, the shared
# expert and bf16 stay at their published values).
CUTS = {
    "num_heads": (128, 16, "8-way TP: 16 of 128 heads"),
    "num_kv_heads": (128, 16, "8-way TP"),
    "d_ff": (18432, 2304, "dense FFN, 18432 / 8"),
    "vocab_size": (129280, 16160, "vocab, 129280 / 8"),
    "num_experts": (256, 8, "32-way EP: 8 of 256 routed experts"),
    "num_layers": (61, 4, "depth: the 3 leading dense layers + 1 MoE"),
}


def chip_share_config():
    """DeepSeek-R1 cut to one chip's share (see CUTS)."""
    from repro.configs import get_config
    base = get_config("deepseek_r1_671b")
    return dataclasses.replace(
        base,
        num_heads=CUTS["num_heads"][1],
        num_kv_heads=CUTS["num_kv_heads"][1],
        d_ff=CUTS["d_ff"][1],
        vocab_size=CUTS["vocab_size"][1],
        num_layers=CUTS["num_layers"][1],
        moe=dataclasses.replace(base.moe,
                                num_experts=CUTS["num_experts"][1]))


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends lowering and compiling, from its own events."""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self.EVENTS:
            self.total += secs


def custom_call_count(cfg, batch: int, max_total: int, page: int) -> int:
    """tpu_custom_call count of the jitted paged decode step the serve
    loop runs, compiled from shapes alone (nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    from repro.core import attn_spec
    from repro.models import model
    from repro.runtime.paged_cache import layout_for

    layout = layout_for(batch, max_total, block_size=page)
    spec = attn_spec.AttnSpec(mode="etap")
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: model.init_paged_cache(cfg, layout))

    def step(p, c, t, table, lengths):
        return model.decode_step(p, cfg, c, t, None, spec=spec,
                                 cache_layout="paged", block_table=table,
                                 lengths=lengths)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, jax.ShapeDtypeStruct((batch,), jnp.int32),
        jax.ShapeDtypeStruct((batch, layout.max_blocks), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.int32)).compile()
    return compiled.as_text().count("tpu_custom_call")


def compare(ref: dict, test: dict) -> dict:
    """Kernel run vs XLA run of one phase, on logits computed from the same
    inputs: every request's last prefill logits (which pick its first
    token) and, for the requests whose first token agrees, its first decode
    step's logits.  Greedy tokens cascade from one near-tie, so the
    whole-stream token agreement is reported, not bounded.  ``argmax_ok``:
    every compared row whose reference top-1 leads its top-2 by more than
    twice the largest logit difference picks the same token in both runs
    — a difference under that margin cannot flip it."""
    import numpy as np
    same = total = 0
    for rid, toks in ref["outputs"].items():
        other = test["outputs"][rid]
        total += len(toks)
        same += sum(int(a == b) for a, b in zip(toks, other, strict=True))
    rows = [(ref["seed_logits"][i], test["seed_logits"][i])
            for i in ref["seed_logits"]]
    fed_same = [i for i in ref["first_decode_logits"]
                if ref["outputs"][i][:1] == test["outputs"][i][:1]]
    rows += [(ref["first_decode_logits"][i], test["first_decode_logits"][i])
             for i in fed_same]
    dmax = max(float(np.max(np.abs(a - b))) for a, b in rows)
    scale = max(float(np.max(np.abs(a))) for a, _ in rows)
    # the phase's decode kernels must meet their twins at least once
    argmax_ok = bool(fed_same) and all(
        int(np.argmax(a)) == int(np.argmax(b)) for a, b in rows
        if np.diff(np.sort(a)[-2:])[0] > 2 * dmax)
    return {"token_agreement": same / max(total, 1), "tokens": total,
            "rows": len(rows), "decode_rows": len(fed_same),
            "max_abs_dlogit": dmax, "max_abs_logit": scale,
            "bound": LOGIT_RTOL * scale,
            "ok": bool(np.isfinite(dmax) and dmax <= LOGIT_RTOL * scale
                       and argmax_ok)}


def run_phase(name: str, argv: list, cfg, clock: CompileClock) -> None:
    """One phase: the same requests served on the kernel path, then on the
    XLA path, through run_paged."""
    import jax

    from repro.kernels import softmax_state
    from repro.launch import serve

    args = serve.parse_args(argv)
    softmax_state.set_default_mode(args.rescale)
    dev = jax.devices()[0]
    out = {}
    for path, use_kernels in (("kernel", True), ("xla", False)):
        c0, t0 = clock.total, time.perf_counter()
        res = serve.run_paged(
            args, dataclasses.replace(cfg, use_kernels=use_kernels),
            record_logits=True)
        wall = time.perf_counter() - t0
        _say(f"phase {name} [{path}]: compile {clock.total - c0:.1f}s, "
             f"wall {wall:.1f}s, tokens served {res['tokens_served']}, "
             f"prefill tokens {res['prefill_tokens']}, prefix hits "
             f"{(res['prefix'] or {}).get('hits', 0)}, peak_bytes_in_use "
             f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
        out[path] = res
    cmp = compare(out["xla"], out["kernel"])
    _say(f"phase {name}: greedy-token agreement {cmp['token_agreement']:.4f} "
         f"over {cmp['tokens']} tokens; max|dlogit| "
         f"{cmp['max_abs_dlogit']:.4g} over {cmp['rows']} logit rows (last "
         f"prefill + first decode step of {cmp['decode_rows']} requests fed "
         f"the same token) vs bound {cmp['bound']:.4g} ({LOGIT_RTOL} x "
         f"max|logit| {cmp['max_abs_logit']:.4g}); "
         f"{'within' if cmp['ok'] else 'OUTSIDE'} bound")
    if not cmp["ok"]:
        raise SystemExit(f"phase {name}: kernel path misses its bound")


def main() -> int:
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    devices = jax.devices()
    dev = devices[0]
    _say(f"platform {dev.platform}, device_kind {dev.device_kind}, "
         f"device count {len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX finds no TPU; this run needs one",
              file=sys.stderr)
        return 1

    from repro.kernels.etap.schedule import plan_splits_paged
    from repro.runtime.paged_cache import layout_for

    cfg = chip_share_config()
    _say("config: deepseek_r1_671b, one chip of 8-way TP x 32-way EP; cuts: "
         + "; ".join(f"{k} {a} -> {b} ({why})"
                     for k, (a, b, why) in CUTS.items()))
    _say("departure: top-8 of 8 held experts sends every token to every "
         "expert (no share of the real router)")
    _say(f"compile cache: {cache_dir}")
    clock = CompileClock()

    # (a) main serve path
    t0 = clock.total
    n_cc = custom_call_count(cfg, batch=16, max_total=2048 + 64, page=64)
    _say(f"phase a: decode step tpu_custom_call count {n_cc} "
         f"(compile {clock.total - t0:.1f}s)")
    if n_cc <= 0:
        raise SystemExit("decode step holds no Pallas kernel")
    run_phase("a", ["--batch", "16", "--requests", "24", "--prompt", "2048",
                    "--gen", "64", "--page-size", "64",
                    "--prefill-chunk", "64", "--shared-prefix", "512",
                    "--seed", "0"], cfg, clock)

    # (b) split-KV: prompts are drawn from {P/2, 3P/4, P}, so P = 32768
    # guarantees a context of at least 16K
    prompt, gen = 32768, 16
    nb = layout_for(1, prompt + gen, block_size=64).max_blocks
    n_splits = plan_splits_paged(1, nb, 64, cfg.num_heads,
                                 cfg.mla.kv_lora_rank).n_splits
    t0 = clock.total
    n_cc = custom_call_count(cfg, batch=1, max_total=prompt + gen, page=64)
    _say(f"phase b: auto plan n_splits {n_splits} over {nb} pages; decode "
         f"step tpu_custom_call count {n_cc} (compile "
         f"{clock.total - t0:.1f}s)")
    if n_splits <= 1:
        raise SystemExit("phase b: the auto plan did not split")
    run_phase("b", ["--batch", "1", "--requests", "3", "--prompt",
                    str(prompt), "--gen", str(gen), "--page-size", "64",
                    "--prefill-chunk", "512", "--no-prefix-cache",
                    "--seed", "0"], cfg, clock)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
