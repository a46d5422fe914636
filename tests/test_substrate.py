"""Checkpointing, data pipeline, weight streaming, serving, configs."""

import dataclasses
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, all_configs, cells, get_config
from repro.models import transformer as tfm
from repro.models.config import SHAPES, ParallelConfig, ShapeConfig
from repro.models.modules import split
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, PrefetchIterator, SyntheticLM
from repro.train.streaming import HostParams, stream_grads, stream_train_step

KEY = jax.random.PRNGKey(0)
PCFG = ParallelConfig(remat="none")


# --------------------------------------------------------------------------
# configs / registry
# --------------------------------------------------------------------------

def test_registry_complete():
    cfgs = all_configs()
    assert len(cfgs) == 11
    spec = {
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
    }
    for name, (L, d, H, kv, ff, V) in spec.items():
        c = cfgs[name]
        assert (c.num_layers, c.d_model, c.n_heads, c.n_kv_heads,
                c.d_ff, c.vocab_size) == (L, d, H, kv, ff, V), name


def test_cell_grid_is_40_with_7_skips():
    """The (arch x shape) grid: 44 cells since zamba2-7b joined the ten
    assigned archs (the name keeps the original 40); its long_500k cell
    runs, so the skips stay 7."""
    rows = list(cells())
    assert len(rows) == 44
    skipped = [(a, s.name) for a, _, s, ok, _ in rows if not ok]
    assert len(skipped) == 7
    assert all(s == "long_500k" for _, s in skipped)
    runnable_long = [a for a, _, s, ok, _ in rows
                     if ok and s.name == "long_500k"]
    assert sorted(runnable_long) == ["mamba2-1.3b", "mixtral-8x7b",
                                     "zamba2-2.7b", "zamba2-7b"]


def test_vocab_padding_divisible_by_16():
    for c in all_configs().values():
        assert c.padded_vocab % 16 == 0
        assert c.padded_vocab >= c.vocab_size
        # flattened qkv dims divisible by 16 (TP over model=16)
        if c.n_heads:
            assert (c.n_heads * c.head_dim) % 16 == 0
            assert (c.n_kv_heads * c.head_dim) % 16 == 0
        if c.d_ff:
            assert c.d_ff % 16 == 0


def test_mesh_fred_device_order():
    from repro.launch.mesh import fred_device_order
    order = fred_device_order(24, mp=4, dp=3, pp=2)
    # MP-consecutive: devices of an MP group are contiguous
    for d in range(3):
        for p in range(2):
            ids = sorted(order[m, d, p] for m in range(4))
            assert ids == list(range(ids[0], ids[0] + 4))


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_atomicity():
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones(5, jnp.bfloat16)}}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, tree, step=3, extras={"step": 3})
        assert ckpt.latest_step(d) == 3
        # an uncommitted dir must be ignored
        fake = Path(d) / "step_00000009"
        fake.mkdir()
        assert ckpt.latest_step(d) == 3
        restored, extras = ckpt.restore(d, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert extras["step"] == 3


def test_checkpoint_crc_detects_corruption():
    tree = {"a": jnp.arange(100.0)}
    with tempfile.TemporaryDirectory() as d:
        path = ckpt.save(d, tree, step=1)
        leaf = path / "leaf_00000.npy"
        raw = bytearray(leaf.read_bytes())
        raw[-1] ^= 0xFF
        leaf.write_bytes(bytes(raw))
        with pytest.raises(IOError):
            ckpt.restore(d, tree)


def test_async_checkpointer_and_gc():
    tree = {"a": jnp.ones(16)}
    with tempfile.TemporaryDirectory() as d:
        ac = ckpt.AsyncCheckpointer(d, keep=2)
        for s in (1, 2, 3, 4):
            ac.save(tree, step=s, extras={"step": s})
        ac.wait()
        ac._gc()
        assert ckpt.latest_step(d) == 4
        steps = sorted(int(p.name[5:]) for p in Path(d).iterdir()
                       if p.name.startswith("step_"))
        assert len(steps) <= 2


def test_retry_io_absorbs_transient_oserrors(monkeypatch):
    from repro.train.faults import FlakyIO
    sleeps = []
    monkeypatch.setattr(ckpt.time, "sleep", sleeps.append)
    # two transient faults < IO_RETRIES attempts: absorbed, with
    # exponential backoff between attempts
    fn = FlakyIO(lambda: "ok", failures=2)
    assert ckpt._retry_io(fn, "probe") == "ok"
    assert fn.calls == 3
    assert sleeps == [ckpt.IO_BACKOFF_S, ckpt.IO_BACKOFF_S * 2]
    # a persistent fault exhausts the budget and re-raises
    stuck = FlakyIO(lambda: "never", failures=100)
    with pytest.raises(OSError):
        ckpt._retry_io(stuck, "probe")
    assert stuck.calls == ckpt.IO_RETRIES


def test_checkpoint_save_and_restore_retry_flaky_io(monkeypatch):
    from repro.train.faults import FlakyIO
    monkeypatch.setattr(ckpt.time, "sleep", lambda _s: None)
    tree = {"a": jnp.arange(6.0), "b": jnp.ones(3, jnp.bfloat16)}
    with tempfile.TemporaryDirectory() as d:
        flaky_save = FlakyIO(np.save, failures=2)
        monkeypatch.setattr(ckpt.np, "save", flaky_save)
        ckpt.save(d, tree, step=1, extras={"step": 1})
        monkeypatch.setattr(ckpt.np, "save", np.save)
        assert flaky_save.calls > 2          # retried through the faults
        assert ckpt.latest_step(d) == 1
        flaky_load = FlakyIO(np.load, failures=2)
        monkeypatch.setattr(ckpt.np, "load", flaky_load)
        restored, extras = ckpt.restore(d, tree)
        monkeypatch.setattr(ckpt.np, "load", np.load)
        assert flaky_load.calls > 2
        assert extras["step"] == 1
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cleanup_incomplete_idempotent_under_race(monkeypatch):
    """Two recoveries sweeping the same dir concurrently: the second
    rmtree of a dir the 'other' recovery already removed must be a
    no-op, not an error — and the count reflects dirs gone."""
    import shutil as _shutil
    with tempfile.TemporaryDirectory() as d:
        root = Path(d)
        ckpt.save(d, {"a": jnp.ones(2)}, step=1)
        d1 = root / "step_00000002.tmp"
        d2 = root / "step_00000003.tmp"
        d1.mkdir()
        d2.mkdir()
        real_rmtree = _shutil.rmtree
        state = {"first": True}

        def racing_rmtree(path, **kw):
            # the interleave: while this recovery handles its first
            # debris dir, the other recovery sweeps the rest
            if state["first"]:
                state["first"] = False
                real_rmtree(d2, ignore_errors=True)
            real_rmtree(path, **kw)

        monkeypatch.setattr(ckpt.shutil, "rmtree", racing_rmtree)
        assert ckpt.cleanup_incomplete(d) == 2       # both dirs gone
        monkeypatch.setattr(ckpt.shutil, "rmtree", real_rmtree)
        assert not d1.exists() and not d2.exists()
        assert ckpt.latest_step(d) == 1              # commits untouched
        assert ckpt.cleanup_incomplete(d) == 0       # second sweep no-op
    # root vanished entirely (recovery racing a teardown): still a no-op
    assert ckpt.cleanup_incomplete(d) == 0


def test_torn_save_leaves_sweepable_debris():
    from repro.train.faults import TornWrite, torn_save
    tree = {"a": jnp.arange(4.0), "b": jnp.ones(2)}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, tree, step=1, extras={"step": 1})
        with pytest.raises(TornWrite):
            torn_save(d, tree, step=2)
        debris = Path(d) / "step_00000002.tmp"
        assert debris.exists()                       # partial leaves only
        assert not (debris / "COMMIT").exists()
        assert not (debris / "MANIFEST.json").exists()
        assert ckpt.latest_step(d) == 1              # torn step invisible
        assert ckpt.cleanup_incomplete(d) == 1
        restored, extras = ckpt.restore(d, tree)
        assert extras["step"] == 1


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

def test_data_deterministic_and_resumable():
    cfg = DataConfig(vocab_size=101, seq_len=16, global_batch=4)
    src = SyntheticLM(cfg)
    b0 = src.batch(5)
    b1 = src.batch(5)
    np.testing.assert_array_equal(b0["tokens"], b1["tokens"])
    it = PrefetchIterator(src, start_step=5)
    got = next(it)
    it.close()
    np.testing.assert_array_equal(got["tokens"], b0["tokens"])
    assert it.state()["step"] == 6


def test_data_has_learnable_structure():
    cfg = DataConfig(vocab_size=64, seq_len=128, global_batch=8)
    b = SyntheticLM(cfg).batch(0)
    toks = b["tokens"]
    match = (toks[:, 7:] == toks[:, :-7]).mean()
    assert match > 0.2          # injected n-gram structure present


# --------------------------------------------------------------------------
# weight streaming
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-1.3b"])
def test_streaming_grads_match_monolithic(arch):
    cfg = get_config(arch).reduced()
    params, _ = split(tfm.init(KEY, cfg))
    batch = {"tokens": jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(KEY, 1),
                                          (2, 16), 0, cfg.vocab_size)}
    loss_ref, grads_ref = jax.value_and_grad(
        lambda p: tfm.loss_fn(p, batch, cfg, PCFG)[0])(params)
    hp = HostParams(params, cfg.num_layers)
    loss_s, g_top, layer_grads = stream_grads(hp, batch, cfg, PCFG)
    assert float(loss_s) == pytest.approx(float(loss_ref), rel=1e-5)
    for i in range(cfg.num_layers):
        ref_i = jax.tree.map(lambda a: np.asarray(a[i]), grads_ref["blocks"])
        for a, b in zip(jax.tree.leaves(ref_i),
                        jax.tree.leaves(layer_grads[i])):
            np.testing.assert_allclose(np.asarray(a), b, atol=5e-6, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(grads_ref["embed"]),
                               np.asarray(g_top["embed"]), atol=5e-6,
                               rtol=1e-4)


@pytest.mark.slow
def test_streaming_training_decreases_loss():
    cfg = get_config("llama3.2-1b").reduced()
    params, _ = split(tfm.init(KEY, cfg))
    hp = HostParams(params, cfg.num_layers)
    batch = {"tokens": jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(KEY, 1),
                                          (2, 16), 0, cfg.vocab_size)}
    losses = [stream_train_step(hp, batch, cfg, PCFG, lr=5e-3)
              for _ in range(4)]
    assert losses[-1] < losses[0]


# --------------------------------------------------------------------------
# serving engine
# --------------------------------------------------------------------------

def test_engine_serves_batch_greedy_matches_decode():
    from repro.serve.engine import Engine, EngineConfig, Request
    cfg = get_config("llama3.2-1b").reduced()
    params, _ = split(tfm.init(KEY, cfg))
    eng = Engine(params, cfg, ecfg=EngineConfig(max_batch=4, cache_len=64))
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8]]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    done = eng.run_batch(reqs)
    for r in done:
        assert len(r.output) == 6
        assert all(0 <= t < cfg.vocab_size for t in r.output)
    # greedy decode is deterministic
    reqs2 = [Request(uid=i, prompt=p, max_new_tokens=6)
             for i, p in enumerate(prompts)]
    done2 = eng.run_batch(reqs2)
    assert [r.output for r in done] == [r.output for r in done2]


def test_engine_spans_leave_tokens_alone(tmp_path):
    """The same seed serves the same tokens with the profiler recording
    the Engine's spans and without it; ``sample_s`` lies within the
    batch, and the trace holds every span the Engine opens."""
    from jax.profiler import ProfileData
    from repro.serve.engine import Engine, EngineConfig, Request
    cfg = get_config("zamba2-2.7b").reduced()
    params, _ = split(tfm.init(KEY, cfg))
    eng = Engine(params, cfg, ecfg=EngineConfig(max_batch=4, cache_len=32))

    def serve():
        reqs = [Request(uid=i, prompt=[1 + i, 2, 3, 4], max_new_tokens=5,
                        temperature=0.7 * (i % 2), top_k=8 * (i % 2))
                for i in range(4)]
        t0 = time.perf_counter()
        eng.run_batch(reqs, seed=2_000_000_011)
        wall = time.perf_counter() - t0
        assert 0.0 <= eng.sample_s <= wall
        return [r.output for r in reqs]

    plain = serve()
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = serve()
    finally:
        jax.profiler.stop_trace()
    assert traced == plain
    path, = tmp_path.rglob("*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert {"engine.run_batch", "engine.prefill", "engine.decode",
            "engine.logits_to_host", "engine.sample"} <= names
    # the programs by their functions' names, not as lambdas
    assert {"PjitFunction(prefill)", "PjitFunction(decode_step)",
            "PjitFunction(_choose)"} <= names


# the Engine's token choice on the device (serve.engine.choose_tokens)

def _choose_steps(logits, temperature, top_k, uid, steps, vocab, seed=7):
    """(steps, B) tokens: the same rows chosen at steps 0 .. steps - 1."""
    from repro.serve.engine import choose_tokens
    top_k = np.asarray(top_k, np.int32)
    args = (jnp.asarray(logits), jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_k), jnp.asarray(uid, jnp.uint32),
            jax.random.PRNGKey(seed))
    return np.asarray(jax.jit(jax.vmap(lambda s: choose_tokens(
        *args, s, vocab=vocab, k=int(top_k.max()))))(jnp.arange(steps)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_choose_greedy_matches_host_argmax(dtype):
    """Greedy rows are ``np.argmax`` of the float32 row's first ``vocab``
    columns, bit for bit: a padded column's larger logit is never chosen,
    and of equal logits the first wins; top_k does not touch them."""
    V, Vp = 50, 64
    logits = np.array(jnp.asarray(
        np.random.default_rng(3).standard_normal((5, Vp)) * 3, dtype))
    logits[1, V + 4] = 100.0                  # best in a padded column
    logits[2, [7, 31]] = 20.0                 # a tie for the best
    logits[3, :V] = 0.0                       # the whole row tied
    got = _choose_steps(logits, [0.0, 0.0, 0.0, 0.0, -1.0], [0, 5, 50, 0, 3],
                        [1, 2, 3, 4, 5], 1, V)[0]
    want = np.asarray(logits, np.float32)[:, :V].argmax(-1)
    assert got.tolist() == want.tolist()
    assert got[2] == 7 and got[3] == 0


def test_choose_draws_within_top_k_ties_kept():
    """Every sampled token lies among the row's ``top_k`` best, those tied
    with the k-th best included; each row keeps its own k."""
    V = 40
    row = np.linspace(-4.0, -1.0, V).astype(np.float32)
    row[[3, 5]] = [3.0, 2.0]
    row[[10, 20, 30]] = 1.5                   # tied at the 3rd best
    logits = np.stack([row, row, row[::-1]])
    steps = _choose_steps(logits, [0.7, 0.7, 1.0], [3, 1, 4], [1, 2, 3],
                          600, V)
    assert set(steps[:, 0]) == {3, 5, 10, 20, 30}
    assert set(steps[:, 1]) == {3}
    assert set(steps[:, 2]) == {V - 1 - i for i in (3, 5, 10, 20, 30)}


def test_choose_top_k_zero_draws_from_whole_vocabulary():
    """``top_k`` 0 at a temperature above 0 draws beyond any top-k: near
    uniform logits over 256 tokens put most draws outside the best 8."""
    V = 256
    row = (0.1 * np.random.default_rng(5).standard_normal(V)).astype(
        np.float32)
    steps = _choose_steps(row[None], [1.0], [0], [9], 200, V)[:, 0]
    assert not set(steps) <= set(np.argsort(row)[-8:].tolist())
    assert len(set(steps)) > 50


def test_choose_same_draw_in_any_slot():
    """The same key, step, uid and row give the same token in any slot of
    any batch, beside any other rows; another step draws anew."""
    V = 64
    rng = np.random.default_rng(11)
    row = rng.standard_normal(V).astype(np.float32)
    small = np.stack([row, rng.standard_normal(V)]).astype(np.float32)
    big = rng.standard_normal((5, V)).astype(np.float32)
    big[3] = row
    got_small = _choose_steps(small, [0.9, 0.0], [0, 0], [42, 1], 40, V)
    got_big = _choose_steps(big, [0.5, 0.0, 0.7, 0.9, 1.3],
                            [4, 0, 8, 0, 0], [7, 8, 9, 42, 10], 40, V)
    assert got_small[:, 0].tolist() == got_big[:, 3].tolist()
    assert len(set(got_small[:, 0])) > 5


def test_choose_samples_the_tempered_top_k_distribution():
    """4000 draws from one fixed row (64 logits, 2 x a standard normal)
    at top-k 8 and T 0.7 lie within 0.05 total variation of
    softmax(top-8 / 0.7), and more than 0.08 from softmax(top-8 / 1.4): a
    wrong temperature is seen.  The row keeps the two exact distributions
    at least 0.15 apart (sampling noise at 4000 draws is under 0.03)."""
    def tv(p, q):
        return 0.5 * float(np.abs(p - q).sum())

    def exact(row, t):
        z = np.where(row >= np.sort(row)[-8], row / t, -np.inf)
        p = np.exp(z - z.max())
        return p / p.sum()

    row = (2 * np.random.default_rng(1).standard_normal(64)).astype(
        np.float32)
    p07, p14 = exact(row, 0.7), exact(row, 1.4)
    assert tv(p07, p14) >= 0.15
    draws = _choose_steps(row[None], [0.7], [8], [5], 4000, 64)[:, 0]
    freq = np.bincount(draws, minlength=64) / len(draws)
    assert tv(freq, p07) < 0.05
    assert tv(freq, p14) > 0.08


# --------------------------------------------------------------------------
# trainer loop (fast end-to-end: init → train → checkpoint → resume)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_trainer_runs_and_resumes():
    from repro.launch.mesh import make_mesh
    from repro.train.train_loop import Trainer, TrainerConfig
    cfg = get_config("llama3.2-1b").reduced()
    shape = ShapeConfig("t", "train", 32, 4)
    mesh = make_mesh((1, 1), ("data", "model"))
    with tempfile.TemporaryDirectory() as d:
        tcfg = TrainerConfig(steps=6, log_every=3, checkpoint_every=3,
                             checkpoint_dir=d)
        tr = Trainer(cfg, shape, mesh, PCFG, tcfg=tcfg)
        tr.run()
        assert ckpt.latest_step(d) == 6
        losses = [h["loss"] for h in tr.history]
        assert losses[-1] < losses[0] + 0.1
        # resume continues from step 6
        tcfg2 = TrainerConfig(steps=8, log_every=2, checkpoint_every=100,
                              checkpoint_dir=d)
        tr2 = Trainer(cfg, shape, mesh, PCFG, tcfg=tcfg2)
        tr2.run()
        assert tr2.step == 8
