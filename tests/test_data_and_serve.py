"""Data-pipeline determinism/sharding + serve-engine behaviour."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.policy import PrecisionPolicy
from repro.data import SyntheticImages, SyntheticLM
from repro.launch.serve import Engine
from repro.models import transformer as T


def test_lm_deterministic_in_seed_step():
    a = SyntheticLM(1000, 32, 8, seed=3).batch(17)
    b = SyntheticLM(1000, 32, 8, seed=3).batch(17)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(1000, 32, 8, seed=4).batch(17)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_lm_labels_shifted():
    b = SyntheticLM(1000, 32, 8, seed=0).batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_lm_host_sharding_disjoint():
    h0 = SyntheticLM(1000, 16, 8, seed=5, host_id=0, num_hosts=2)
    h1 = SyntheticLM(1000, 16, 8, seed=5, host_id=1, num_hosts=2)
    assert h0.host_batch == h1.host_batch == 4
    t0, t1 = h0.batch(0)["tokens"], h1.batch(0)["tokens"]
    assert not np.array_equal(t0, t1)   # different streams per host


def test_images_resume_bit_identical():
    d = SyntheticImages()
    x1 = d.batch(42, 32)["x"]
    x2 = SyntheticImages().batch(42, 32)["x"]
    np.testing.assert_array_equal(x1, x2)


def test_engine_greedy_deterministic():
    cfg = configs.get_smoke("llama3_8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, PrecisionPolicy("float32"), params, max_len=48)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                 cfg.vocab_size)
    out1 = eng.generate(prompts, max_new=6)
    out2 = eng.generate(prompts, max_new=6)
    assert out1.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_engine_matches_teacher_forcing():
    """Greedy decode == argmax of full forward at every position."""
    cfg = configs.get_smoke("llama3_8b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pol = PrecisionPolicy("float32")
    eng = Engine(cfg, pol, params, max_len=64)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                                 cfg.vocab_size)
    out = np.asarray(eng.generate(prompts, max_new=4))

    toks = prompts
    for i in range(4):
        logits, _, _ = T.forward(cfg, pol, params, {"tokens": toks},
                                 eng.exps, eng.sinks, mode="train")
        nxt = int(jnp.argmax(logits[0, -1]))
        assert nxt == int(out[0, i]), f"step {i}: {nxt} != {out[0, i]}"
        toks = jnp.concatenate([toks, jnp.array([[nxt]])], axis=1)


def test_serve_cli_constructs_serve_engine(capsys):
    """The CLI drives the repro.serve engine end-to-end (mixed lengths)."""
    from repro.launch.serve import main
    main(["--arch", "llama3_8b", "--smoke", "--arithmetic", "float32",
          "--num-requests", "2", "--prompt-len", "4,6", "--max-new", "2",
          "--slots", "2", "--cache-bits", "8"])
    out = capsys.readouterr().out
    assert "served 2 requests" in out
    assert "tok/s" in out


def test_compile_cache_placement(monkeypatch):
    """The entry points' cache helper: JAX_COMPILATION_CACHE_DIR wins and
    nothing else is set; otherwise a fixed <checkout>/.cache/jax, the same
    .cache/ the autotune table defaults to."""
    import pathlib

    from repro import compile_cache
    from repro.kernels import dispatch
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
        assert compile_cache.use_compile_cache() == "/elsewhere/jax"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        root = pathlib.Path(__file__).resolve().parents[1]
        assert path == str(root / ".cache" / "jax")
        assert jax.config.jax_compilation_cache_dir == path
        assert dispatch._CACHE_DEFAULT == str(root / ".cache"
                                              / "autotune.json")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
