"""First-ever tests for the launch/serving stack: the
prefill -> adapt -> decode path on a reduced config, the engine
builders in `launch.serve`, the decode-attention `use_impl` scope, and
the example + launcher entry points as CI-runnable subprocesses."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.kernels.decode_attention import ops as dec_ops
from repro.launch.serve import build_engine, build_serving_fns

REPO = Path(__file__).resolve().parents[1]


def _env():
    env = dict(os.environ)
    src = str(REPO / "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    return env


class TestServingFns:
    def test_prefill_then_decode_shapes_and_cache(self):
        """The serve entry points compose: prefill yields last-position
        logits + a cache the decode step advances one token at a time."""
        cfg = reduced_config(get_config("smollm-360m"))
        from repro.models import init_lm
        params = init_lm(jax.random.PRNGKey(0), cfg)
        prefill, decode = build_serving_fns(cfg)
        rng = np.random.RandomState(0)
        B, L = 2, 16
        prompts = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, L)),
                              jnp.int32)
        logits, cache = jax.jit(prefill)(params, prompts)
        assert logits.shape == (B, cfg.vocab_size)
        assert int(cache["length"]) == L
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        logits2, cache = jax.jit(decode)(params, cache, tok)
        assert logits2.shape == (B, cfg.vocab_size)
        assert int(cache["length"]) == L + 1

    def test_build_engine_serves_end_to_end(self):
        """build_engine wires algorithm + serve fns + cache into an
        engine that adapts and decodes (the example's path, inline)."""
        cfg = reduced_config(get_config("smollm-360m"))
        engine = build_engine(cfg, adapt_batch=2, cache_capacity=4, seed=0)
        from repro.federated.serving import TrafficModel
        tm = TrafficModel(num_clients=2, rate=50.0, support_sizes=(2,),
                          seed=0)
        reqs = tm.requests(
            3,
            lambda r, size: jnp.asarray(
                r.randint(0, cfg.vocab_size, (size, 16)), jnp.int32),
            lambda r: jnp.asarray(
                r.randint(0, cfg.vocab_size, (8,)), jnp.int32))
        report = engine.serve(reqs, max_new_tokens=2)
        s = report.summary()
        assert s["requests"] == 3
        assert s["hits"] + s["misses"] == 3
        for rec in report.records:
            assert rec["tokens"].shape == (2,)
            assert (0 <= rec["tokens"]).all()
            assert (rec["tokens"] < cfg.vocab_size).all()

    def test_use_impl_scopes_and_restores(self):
        prev = dec_ops.resolve_impl()
        assert prev == "xla"                    # the CPU platform's pick
        with dec_ops.use_impl("pallas_interpret"):
            assert dec_ops.resolve_impl() == "pallas_interpret"
            assert dec_ops.resolve_impl("xla") == "xla"   # explicit wins
        assert dec_ops.resolve_impl() == prev
        try:
            with dec_ops.use_impl("xla"):
                raise RuntimeError("boom")
        except RuntimeError:
            pass
        assert dec_ops.resolve_impl() == prev   # restored on exception
        with pytest.raises(ValueError):
            with dec_ops.use_impl("cuda"):
                pass


class TestEntryPoints:
    def test_example_dry_run(self):
        """examples/serve_personalized.py --dry-run: the CI smoke for
        the full traffic -> adapt -> cache -> prefill -> decode path."""
        out = subprocess.run(
            [sys.executable, "examples/serve_personalized.py", "--dry-run",
             "--arch", "smollm-360m"],
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "served 4 requests" in out.stdout
        assert "sample:" in out.stdout

    def test_launch_serve_reduced(self):
        """python -m repro.launch.serve --reduced: the decode launcher
        runs on the host mesh (covers the perf_counter step timing)."""
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "smollm-360m", "--shape", "decode_32k", "--steps", "2",
             "--reduced"],
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "decode step 1" in out.stdout

    def test_launch_train_reduced(self):
        """python -m repro.launch.train --reduced: the meta-training
        launcher runs its device mesh end to end on a CPU."""
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.train", "--arch",
             "smollm-360m", "--shape", "train_4k", "--steps", "2",
             "--reduced"],
            cwd=REPO, env=_env(), capture_output=True, text=True,
            timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "step    2  loss=" in out.stdout


def test_per_chip_shape_is_a_sixteenth_of_each_client():
    """One chip of the production data axis holds seqs_per_client/16 of
    every client's sequences; n chips hold n such shares."""
    from repro.configs import INPUT_SHAPES
    from repro.launch.mesh import PRODUCTION_DATA, make_device_mesh
    from repro.launch.train import per_chip_shape
    full = INPUT_SHAPES["train_4k"]
    one = per_chip_shape(full, 1)
    assert (one.seqs_per_client, one.clients_per_round, one.seq_len) == \
        (full.seqs_per_client // PRODUCTION_DATA, 8, 4096)
    assert one.global_batch == 16
    assert per_chip_shape(full, 4).seqs_per_client == 8
    assert per_chip_shape(full, PRODUCTION_DATA) == full
    mesh = make_device_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (jax.device_count(), 1)
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)

