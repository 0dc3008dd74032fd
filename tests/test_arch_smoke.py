"""Per-architecture smoke tests: reduced same-family config, one train (or
forward) step on CPU, asserting output shapes and no NaNs — as required for
every assigned architecture."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import PrecisionPolicy
from repro.data import SyntheticLM
from repro.models import transformer as T
from repro.optim.opt import OptConfig, sgd_init
from repro.train import init_train_state, make_train_step

POLICY = PrecisionPolicy("dfxp", comp_width=10, update_width=12,
                         update_interval=5)
B, S = 2, 32


def _batch(cfg, key):
    if cfg.input_mode == "tokens":
        lm = SyntheticLM(cfg.vocab_size, S, B, seed=0)
        b = lm.batch(0)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "labels": jnp.asarray(b["labels"])}
    else:
        batch = {"embeds": jax.random.normal(key, (B, S, cfg.d_model)) * 0.1,
                 "labels": jax.random.randint(key, (B, S), 0,
                                              cfg.vocab_size)}
        if cfg.mrope_sections:
            batch["positions"] = jnp.broadcast_to(jnp.arange(S), (3, B, S))
    if cfg.encoder_layers:
        batch["src_embeds"] = jax.random.normal(key, (B, 16, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_arch_smoke_train_step(arch):
    cfg = configs.get_smoke(arch)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    gs = T.group_shapes(cfg)
    opt_cfg = OptConfig(kind="sgd", lr=0.01, lr_decay_steps=100)
    state = init_train_state(params, sgd_init(params), gs, POLICY,
                             init_exp=-12.0)

    def loss_fn(p, b, s, exps):
        return T.loss_fn(cfg, POLICY, p, b, exps, s)

    step = jax.jit(make_train_step(loss_fn, gs, POLICY, opt_cfg))
    batch = _batch(cfg, key)
    state2, metrics = step(state, batch, key)
    assert jnp.isfinite(metrics["loss"]), f"{arch}: non-finite loss"
    assert jnp.isfinite(metrics["grad_norm"]), f"{arch}: non-finite grads"
    assert int(state2.step) == 1
    # params changed and stayed finite
    moved = jax.tree.map(lambda a, b: jnp.any(a != b), state.params,
                         state2.params)
    assert any(bool(x) for x in jax.tree.leaves(moved)), f"{arch}: no update"
    for leaf in jax.tree.leaves(state2.params):
        assert bool(jnp.all(jnp.isfinite(leaf))), f"{arch}: non-finite param"


@pytest.mark.parametrize("fused", [False, True], ids=["composite", "fused"])
@pytest.mark.parametrize("arch", configs.ARCHS)
def test_arch_dots_run_at_full_precision(arch, fused,
                                         assert_dots_full_precision):
    """Every dot of the DFXP loss and its gradient — model einsums,
    the tape's matmuls or the fused qmatmul kernels — keeps f32 products."""
    cfg = configs.get_smoke(arch)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    gs = T.group_shapes(cfg)
    pol = PrecisionPolicy("dfxp", fused_matmul=fused)
    from repro.core import ScaleState
    exps = ScaleState.create(gs, -6.0).exps
    sinks = {n: jnp.zeros(s + (3,), jnp.float32) for n, s in gs.items()
             if n.startswith("g:")}
    assert_dots_full_precision(
        jax.grad(lambda p, b: T.loss_fn(cfg, pol, p, b, exps, sinks)[0]),
        params, _batch(cfg, key))


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_arch_smoke_forward_shapes(arch):
    cfg = configs.get_smoke(arch)
    key = jax.random.PRNGKey(1)
    params = T.init_params(cfg, key)
    gs = T.group_shapes(cfg)
    from repro.core import ScaleState
    st = ScaleState.create(gs, -6.0)
    sinks = {n: jnp.zeros(s + (3,), jnp.float32) for n, s in gs.items()
             if n.startswith("g:")}
    batch = _batch(cfg, key)
    batch.pop("labels")
    logits, stats, _ = T.forward(cfg, PrecisionPolicy("float32"), params,
                                 batch, st.exps, sinks, mode="train")
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: NaN logits"


@pytest.mark.parametrize("arch", ["llama3_8b", "mamba2_370m",
                                  "granite_moe_1b", "zamba2_1p2b",
                                  "seamless_m4t_medium"])
def test_arch_smoke_decode(arch):
    cfg = configs.get_smoke(arch)
    key = jax.random.PRNGKey(2)
    params = T.init_params(cfg, key)
    gs = T.group_shapes(cfg)
    from repro.core import ScaleState
    st = ScaleState.create(gs, -6.0)
    sinks = {n: jnp.zeros(s + (3,), jnp.float32) for n, s in gs.items()
             if n.startswith("g:")}
    pol = PrecisionPolicy("float32")
    batch = _batch(cfg, key)
    batch.pop("labels")
    _, _, cache = T.prefill(cfg, pol, params, batch, st.exps, sinks,
                            max_cache_len=S + 8)
    tok = jnp.zeros((B,), jnp.int32)
    logits, _, cache2 = T.decode_step(cfg, pol, params, cache, tok, S,
                                      st.exps, sinks)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_ssm_long_chunk_grads_finite():
    """At a published chunk length (mamba2_370m: 256) the cumulative
    log-decay spans hundreds of nats, so ``exp`` above the causal
    diagonal overflows; masking it after the exp made every gradient
    NaN while the loss stayed finite."""
    from repro.core.tape import QTape
    from repro.models.ssm import SSMSpec, init_ssm, ssm_forward

    spec = SSMSpec(d_model=64, state=16, headdim=32, chunk=256)
    params = init_ssm(jax.random.PRNGKey(0), spec)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 256, spec.d_model))
    tape = QTape(PrecisionPolicy("float32"), {}, {})

    def loss(p):
        return jnp.sum(ssm_forward(p, spec, u, tape, "ssm")[0] ** 2)

    val, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert bool(jnp.isfinite(val))
    for name, g in grads.items():
        assert bool(jnp.all(jnp.isfinite(g))), name
