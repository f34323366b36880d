"""End-to-end system behaviour: training convergence, microbatch-accumulation
equivalence, optimizers, ETAP core equivalences inside the full model, data
pipeline determinism, and a miniature sharded end-to-end run."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.steps import TrainConfig, make_train_step
from repro.models import model
from repro.optim import optimizers as opt


def _setup(arch="smollm_360m", **tkw):
    cfg = reduced(get_config(arch))
    tcfg = TrainConfig(optimizer=opt.OptimizerConfig(
        lr=1e-3, warmup_steps=2, total_steps=50, **tkw.pop("okw", {})), **tkw)
    params = model.init(jax.random.PRNGKey(0), cfg)
    opt_state = opt.opt_init(tcfg.optimizer, params)
    return cfg, tcfg, params, opt_state


def test_training_reduces_loss_on_learnable_data():
    """Train on a tiny fixed batch — loss must drop hard (memorization)."""
    cfg, tcfg, params, opt_state = _setup()
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                          0, cfg.vocab_size)}
    first = None
    for s in range(30):
        params, opt_state, m = step_fn(params, opt_state, batch, s)
        first = first or float(m["nll"])
    assert float(m["nll"]) < first * 0.7, (first, float(m["nll"]))


def test_grad_accumulation_equivalence():
    """n_micro=4 must equal n_micro=1 up to accumulation-dtype rounding."""
    cfg, _, params, _ = _setup()
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (8, 32),
                                          0, cfg.vocab_size)}
    outs = {}
    for n in (1, 4):
        tcfg = TrainConfig(optimizer=opt.OptimizerConfig(lr=1e-3),
                           n_micro=n)
        opt_state = opt.opt_init(tcfg.optimizer, params)
        p2, _, m = make_train_step(cfg, tcfg)(params, opt_state, batch, 0)
        outs[n] = (jax.tree.leaves(p2), float(m["nll"]))
    assert abs(outs[1][1] - outs[4][1]) < 1e-2
    for a, b in zip(outs[1][0], outs[4][0], strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-2)


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizers_step_and_descend(name):
    cfg, tcfg, params, _ = _setup(okw={"name": name})
    ocfg = tcfg.optimizer
    state = opt.opt_init(ocfg, params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab_size)}
    l0, _ = model.loss_fn(params, cfg, batch)
    for _ in range(10):
        grads, _ = jax.grad(lambda p: model.loss_fn(p, cfg, batch),
                            has_aux=True)(params)
        grads, _ = opt.clip_by_global_norm(grads, 1.0)
        params, state = opt.opt_update(ocfg, grads, state, params)
    l1, _ = model.loss_fn(params, cfg, batch)
    assert float(l1) < float(l0)


def test_adafactor_state_is_factored():
    cfg, _, params, _ = _setup(okw={"name": "adafactor",
                                    "min_dim_size_to_factor": 8})
    state = opt.opt_init(opt.OptimizerConfig(name="adafactor",
                                             min_dim_size_to_factor=8), params)
    leaves = jax.tree_util.tree_flatten_with_path(state["v"])[0]
    assert any("vr" in "".join(str(p) for p in kp) for kp, _ in leaves)
    # factored stats are ~sqrt the size of the full moment
    n_v = sum(l.size for _, l in leaves)
    n_p = sum(l.size for l in jax.tree.leaves(params))
    assert n_v < 0.5 * n_p


def test_data_pipeline_determinism_and_sharding_split():
    cfg = reduced(get_config("qwen3_8b"))
    d = DataConfig(seed=5, global_batch=8, seq_len=16)
    a = make_batch(cfg, d, step=3)
    b = make_batch(cfg, d, step=3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = make_batch(cfg, d, step=4)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # host shard [2,6) equals the slice of the global batch (restart safety)
    part = make_batch(cfg, d, step=3, lo=2, hi=6)
    np.testing.assert_array_equal(part["tokens"], a["tokens"][2:6])


def test_loss_fn_matches_manual_cross_entropy():
    cfg, _, params, _ = _setup("stablelm_1_6b")
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                                cfg.vocab_size)
    logits, _, _ = model.forward(params, cfg, {"tokens": tokens})
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    manual = -np.mean([lp[b, t, tokens[b, t + 1]]
                       for b in range(2) for t in range(11)])
    loss, metrics = model.loss_fn(params, cfg, {"tokens": tokens})
    np.testing.assert_allclose(float(metrics["nll"]), manual, rtol=1e-5)


def test_layer_grouping_plans():
    """Grouping compiles each distinct block body once (DESIGN.md §3)."""
    g = model.layer_groups(get_config("qwen3_8b"))
    assert len(g) == 1 and g[0]["n"] == 36
    g = model.layer_groups(get_config("recurrentgemma_9b"))
    assert g[0]["sigs"] == [("rglru", False), ("rglru", False), ("attn", False)]
    assert g[0]["n"] == 12 and len(g) == 3          # 12 cycles + 2 tail layers
    g = model.layer_groups(get_config("deepseek_r1_671b"))
    assert [x["n"] for x in g] == [3, 58]           # dense prefix + MoE stack
    total = sum(x["n"] * len(x["sigs"]) for x in g)
    assert total == 61


def test_etap_used_in_model_decode_matches_kernel():
    """The model's decode path and the Pallas kernel agree on real MLA
    activations (not just synthetic tensors)."""
    import dataclasses
    cfg = reduced(get_config("deepseek_r1_671b"))
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    params = model.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)
    _, cache, pos = model.prefill(params, cfg, {"tokens": tokens[:, :8]},
                                  max_len=16)
    d_xla, _ = model.decode_step(params, cfg, cache, tokens[:, 8], pos)
    d_krn, _ = model.decode_step(params, cfg_k, cache, tokens[:, 8], pos)
    np.testing.assert_allclose(np.asarray(d_xla), np.asarray(d_krn), atol=2e-4)


def test_serve_kernel_and_xla_paths_agree(chip_smoke):
    """run_paged on the Pallas kernels and on the XLA twins: the same
    tokens, and the per-request logits chip_smoke.py compares (each
    request's last prefill logits and first decode step) agree — fp32
    paths match to rounding, so the chip run's bound is all bf16."""
    import dataclasses

    from repro.launch import serve

    cfg = reduced(get_config("deepseek_r1_671b"))
    args = serve.parse_args(["--reduced", "--batch", "2", "--requests", "3",
                             "--prompt", "24", "--gen", "4",
                             "--prefill-chunk", "8", "--shared-prefix", "8"])
    res = {uk: serve.run_paged(args, dataclasses.replace(cfg, use_kernels=uk),
                               record_logits=True)
           for uk in (True, False)}
    assert res[True]["outputs"] == res[False]["outputs"]
    for key in ("seed_logits", "first_decode_logits"):
        assert sorted(res[True][key]) == [0, 1, 2]
        for rid, row in res[False][key].items():
            np.testing.assert_allclose(res[True][key][rid], row, atol=1e-5)
    cmp = chip_smoke.compare(res[False], res[True])
    assert cmp["ok"] and cmp["token_agreement"] == 1.0
    assert cmp["decode_rows"] == 3
