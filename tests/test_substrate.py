"""Substrate tests: checkpoint/restart, elastic reshard, watchdog, data
determinism, gradient compression, optimizer behaviour."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis or skip-stub shim

from repro.checkpoint import CheckpointManager
from repro.data import TokenStream
from repro.models import api
from repro.models.common import ArchConfig
from repro.optim.adamw import AdamW, apply_updates, global_norm
from repro.optim.compression import compress_int8, decompress_int8
from repro.runtime import FaultInjector, StepWatchdog, run_with_restarts

CFG = ArchConfig(name="tt", family="dense", n_layers=2, d_model=32,
                 n_heads=2, n_kv_heads=2, d_ff=64, vocab=128, head_dim=16,
                 microbatches=1, compute_dtype="float32")


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        model = api.build(CFG)
        params = model.init(jax.random.key(0))
        opt = AdamW()
        opt_state = opt.init(params)
        mgr = CheckpointManager(tmp_path)
        mgr.save(7, params, opt_state)
        step, tree = mgr.restore({"params": params,
                                  "opt_state": opt_state})
        assert step == 7
        for a, b in zip(jax.tree.leaves(tree["params"]),
                        jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_atomicity_tmp_never_visible(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, {"w": jnp.ones((4,))})
        files = [p.name for p in tmp_path.iterdir()]
        assert not any(f.endswith(".tmp") for f in files)
        assert mgr.latest_step() == 1

    def test_async_save_and_retention(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        for s in range(5):
            mgr.save(s, {"w": jnp.full((8,), float(s))}, blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 4
        ckpts = sorted(tmp_path.glob("step_*.npz"))
        assert len(ckpts) == 2  # retention

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_crash_mid_save_keeps_previous_checkpoint(self, tmp_path,
                                                      monkeypatch):
        """A crash during the npz write must not tear the latest pointer:
        the partial file stays a ``.tmp``, never a published step."""
        mgr = CheckpointManager(tmp_path)
        mgr.save_tree(1, {"w": jnp.ones((4,))})
        assert mgr.latest_step() == 1

        real_savez = np.savez

        def torn_savez(f, **arrs):
            f.write(b"PK\x03\x04 torn")       # partial bytes, then die
            raise OSError("disk died mid-write")

        monkeypatch.setattr(np, "savez", torn_savez)
        mgr.save_tree(2, {"w": jnp.full((4,), 2.0)}, blocking=False)
        mgr.wait()                             # crash happens on the thread
        monkeypatch.setattr(np, "savez", real_savez)

        assert mgr.latest_step() == 1          # step 2 never published
        step, tree = mgr.restore_tree({"w": np.zeros(4, np.float32)})
        assert step == 1
        np.testing.assert_array_equal(np.asarray(tree["w"]), np.ones(4))
        published = list(tmp_path.glob("step_*.npz"))
        assert all("00000002" not in p.name for p in published)

    def test_save_tree_arbitrary_pytree_roundtrip(self, tmp_path):
        """save_tree/restore_tree handle non-train-shaped pytrees (the
        serving snapshot shape) including manifest extra metadata."""
        mgr = CheckpointManager(tmp_path)
        tree = {"standing": np.arange(12.0, dtype=np.float32).reshape(3, 4),
                "dyn": {"fwd": {"col": np.arange(5, dtype=np.int32)},
                        "counts": (np.int64(7), np.int64(9))}}
        mgr.save_tree(4, tree, extra={"round": 2, "acked": 2})
        step, got = mgr.restore_tree(jax.tree.map(np.zeros_like, tree))
        assert step == 4
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert mgr.manifest_extra(4) == {"round": 2, "acked": 2}

    def test_elastic_reshard_restore(self, tmp_path):
        """Checkpoint written unsharded restores under a different mesh."""
        from repro.checkpoint.manager import restore_resharded
        from jax.sharding import PartitionSpec as P
        mgr = CheckpointManager(tmp_path)
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        mgr.save(3, tree["w"])
        mesh = jax.make_mesh((1,), ("data",))
        step, placed = restore_resharded(
            mgr, {"params": tree["w"]}, mesh,
            {"params": P("data", None)})
        assert step == 3
        np.testing.assert_array_equal(np.asarray(placed["params"]),
                                      np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

class TestRestart:
    def test_restart_reproduces_failure_free_run(self, tmp_path):
        """Injected failures must not change the final state (determinism)."""
        def make_step(injector=None):
            def step(i, state):
                if injector:
                    injector.maybe_fail(i)
                return {"params": {"w": state["params"]["w"] + i}}, {"i": i}
            return step

        init = {"params": {"w": jnp.zeros(())}}
        clean, _ = run_with_restarts(
            make_step(), init, 20, CheckpointManager(tmp_path / "a"),
            checkpoint_every=5)
        inj = FaultInjector({7, 13})
        faulty, summary = run_with_restarts(
            make_step(inj), init, 20, CheckpointManager(tmp_path / "b"),
            checkpoint_every=5)
        assert summary["failures"] == 2
        assert float(clean["params"]["w"]) == float(faulty["params"]["w"])

    def test_gives_up_after_max_failures(self, tmp_path):
        inj = FaultInjector(set(range(100)))
        inj.fired = set()  # re-fire every time

        def step(i, state):
            raise RuntimeError("always down")

        with pytest.raises(RuntimeError):
            run_with_restarts(step, {"params": {"w": jnp.zeros(())}}, 5,
                              CheckpointManager(tmp_path), max_failures=2)

    def test_restarts_generic_pytree_state(self, tmp_path):
        """State is any pytree, not the train-shaped dict — a serving
        carry {standing results, counters} restarts identically."""
        def make_step(injector=None):
            def step(i, state):
                if injector:
                    injector.maybe_fail(i)
                return {"res": state["res"] + i,
                        "meta": (state["meta"][0] + 1,)}, {}
            return step

        init = {"res": jnp.zeros((2, 3)), "meta": (jnp.zeros((), jnp.int32),)}
        clean, _ = run_with_restarts(
            make_step(), init, 9, CheckpointManager(tmp_path / "a"),
            checkpoint_every=3)
        faulty, summary = run_with_restarts(
            make_step(FaultInjector({4})), init, 9,
            CheckpointManager(tmp_path / "b"), checkpoint_every=3)
        assert summary["failures"] == 1
        np.testing.assert_array_equal(np.asarray(clean["res"]),
                                      np.asarray(faulty["res"]))
        assert int(clean["meta"][0]) == int(faulty["meta"][0]) == 9

    def test_non_retryable_surfaces_immediately(self, tmp_path):
        """Programming bugs are not in the retryable whitelist: no restart
        is burned, the error propagates on the first occurrence."""
        calls = []

        def step(i, state):
            calls.append(i)
            raise ValueError("a bug, not a dead worker")

        with pytest.raises(ValueError):
            run_with_restarts(step, {"w": jnp.zeros(())}, 5,
                              CheckpointManager(tmp_path), max_failures=3)
        assert calls == [0]   # never retried


class TestWatchdog:
    def test_flags_straggler(self):
        wd = StepWatchdog(warmup_steps=3)
        flagged = []
        wd.on_straggler = lambda s, d, m: flagged.append(s)
        for i in range(20):
            wd.report(i, 0.1 + 0.001 * (i % 3))
        assert not flagged
        wd.report(20, 1.0)  # 10× slower
        assert flagged == [20]

    def test_ewma_tracks_drift(self):
        wd = StepWatchdog(warmup_steps=2, alpha=0.5)
        for i in range(30):
            wd.report(i, 0.1 if i < 15 else 0.2)
        assert 0.15 < wd.mean_step_s <= 0.21


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

class TestData:
    def test_seekable_determinism(self):
        s1 = TokenStream(CFG, batch=4, seq=16, seed=3)
        s2 = TokenStream(CFG, batch=4, seq=16, seed=3)
        np.testing.assert_array_equal(np.asarray(s1.batch_at(9)["tokens"]),
                                      np.asarray(s2.batch_at(9)["tokens"]))

    def test_steps_differ(self):
        s = TokenStream(CFG, batch=4, seq=16, seed=3)
        a = np.asarray(s.batch_at(0)["tokens"])
        b = np.asarray(s.batch_at(1)["tokens"])
        assert (a != b).any()

    def test_zipf_skew(self):
        """Heavy-hitter tokens exist — the degree-skew analogue."""
        s = TokenStream(CFG, batch=64, seq=64, seed=0)
        toks = np.asarray(s.batch_at(0)["tokens"]).ravel()
        counts = np.bincount(toks, minlength=CFG.vocab)
        assert counts.max() > 20 * max(np.median(counts), 1)


# ---------------------------------------------------------------------------
# optimizer + compression
# ---------------------------------------------------------------------------

class TestOptim:
    def test_adamw_descends_quadratic(self):
        opt = AdamW(learning_rate=0.1, weight_decay=0.0, warmup_steps=1)
        p = {"w": jnp.array([3.0, -2.0])}
        st_ = opt.init(p)
        for _ in range(200):
            g = {"w": 2 * p["w"]}
            up, st_ = opt.update(g, st_, p)
            p = apply_updates(p, up)
        assert float(jnp.abs(p["w"]).max()) < 0.05

    def test_clipping_bounds_update(self):
        opt = AdamW(learning_rate=1.0, clip_norm=1.0, warmup_steps=1)
        p = {"w": jnp.zeros(4)}
        st_ = opt.init(p)
        g = {"w": jnp.full(4, 1e6)}
        up, _ = opt.update(g, st_, p)
        assert np.isfinite(np.asarray(up["w"])).all()

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 2000))
    def test_int8_compression_bounded_error(self, seed, n):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=n) * rng.uniform(0.1, 100),
                        jnp.float32)
        q, s, meta = compress_int8(x)
        deq = decompress_int8(q, s, meta)
        # per-block max error ≤ scale/2 = |block|_max / 254
        blocks = np.asarray(x)
        err = np.abs(np.asarray(deq) - blocks)
        assert err.max() <= np.abs(blocks).max() / 254 + 1e-6

    def test_error_feedback_converges(self):
        """Quantized-gradient SGD with error feedback still descends."""
        w = np.array([5.0, -5.0, 2.0], dtype=np.float32)
        e = np.zeros_like(w)
        for _ in range(300):
            g = 2 * w
            q, s, meta = compress_int8(jnp.asarray(g + e))
            deq = np.asarray(decompress_int8(q, s, meta))
            e = g + e - deq
            w = w - 0.05 * deq
        assert np.abs(w).max() < 0.1


# ---------------------------------------------------------------------------
# persistent compile cache location (repro.compile_cache)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir_honours_env(monkeypatch, env_dir):
    """The env var is left to JAX untouched; without it the cache sits at
    the fixed in-repo path."""
    import pathlib

    from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = enable_compile_cache()
        if env_dir is None:
            assert got == str(REPO_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == got
            assert REPO_CACHE_DIR == (pathlib.Path(__file__).resolve()
                                      .parents[1] / ".jax_cache")
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
