"""Multi-device tests in subprocesses (forced host devices): pipeline
parallelism, sharded train step with collectives, distributed join on a
mesh. Subprocesses keep the main test session at 1 device."""
import json
import subprocess
import sys
import textwrap

import pytest


def _run(code: str, timeout: int = 900, devices: int = 8) -> str:
    prelude = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        "import sys\n"
        "sys.path.insert(0, 'src')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout,
        cwd=__file__.rsplit("/", 2)[0])
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_pipeline_parallel_gpipe_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.pipeline import (gpipe_forward, make_pp_mesh,
                                         split_stages, bubble_fraction)
        S, L, M, mb, dim = 4, 8, 4, 2, 16
        mesh = make_pp_mesh(S)
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(scale=0.3, size=(L, dim, dim)),
                        jnp.float32)

        def layer(wi, x):
            return jnp.tanh(x @ wi)

        def stage_fn(params, x):   # params: (L/S, dim, dim)
            for i in range(params.shape[0]):
                x = layer(params[i], x)
            return x

        x = jnp.asarray(rng.normal(size=(M, mb, dim)), jnp.float32)
        stage_params = split_stages(w, S)
        fwd = gpipe_forward(stage_fn, mesh, M)
        y_pp = fwd(stage_params, x)
        # sequential reference
        y_ref = x
        for i in range(L):
            y_ref = layer(w[i], y_ref)
        np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
        assert 0 < bubble_fraction(S, M) < 1
        print('PP-OK')
    """)
    assert "PP-OK" in out


def test_sharded_train_step_runs_with_collectives():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, smoke_config
        from repro.models import build_model
        from repro.launch.steps import make_train_step, batch_shardings
        from repro.dist import sharding as shd
        from repro.train.optimizer import AdamW, AdamWConfig

        mesh = jax.make_mesh((4, 2), ('data', 'model'))
        cfg = smoke_config(get_config('qwen3-0.6b'))
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        opt = AdamW(AdamWConfig(learning_rate=1e-3))
        opt_state = opt.init(params)
        step = make_train_step(m, opt)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                    cfg.vocab)
        batch = {'tokens': tokens, 'labels': tokens}
        shd.set_mesh(mesh)
        with mesh:
            p_sh = shd.param_shardings(params, mesh)
            params = jax.device_put(params, p_sh)
            jitted = jax.jit(step)
            new_params, new_state, metrics = jitted(params, opt_state, batch)
            loss_sharded = float(metrics['loss'])
        shd.set_mesh(None)
        # single-device reference
        params1 = m.init(jax.random.PRNGKey(0))
        _, _, metrics1 = jax.jit(step)(params1, opt.init(params1), batch)
        assert abs(loss_sharded - float(metrics1['loss'])) < 1e-2, \\
            (loss_sharded, float(metrics1['loss']))
        print('SHARD-OK', loss_sharded)
    """)
    assert "SHARD-OK" in out


def test_distributed_join_on_mesh_matches_truth():
    out = _run("""
        import jax, numpy as np, tempfile, os
        from repro.core import (JoinConfig, bucketize, build_bucket_graph,
                                recall)
        from repro.core.distributed import DistributedJoin
        from repro.data import clustered_vectors, brute_force_pairs
        from repro.store.vector_store import FlatVectorStore

        mesh = jax.make_mesh((8,), ('data',))
        x = clustered_vectors(3000, 32, seed=4)
        eps = 0.3
        d = tempfile.mkdtemp()
        store = FlatVectorStore.from_array(os.path.join(d, 'x.bin'), x)
        cfg = JoinConfig(epsilon=eps, recall_target=0.95, pad_align=64,
                         memory_budget_bytes=2 << 20, num_buckets=16)
        bs, meta, _ = bucketize(store, os.path.join(d, 'bk'), cfg)
        graph = build_bucket_graph(meta, cfg)
        pairs, info = DistributedJoin(bs, meta, cfg, mesh=mesh).run(graph)
        truth = brute_force_pairs(x, eps)
        r = recall(pairs, truth)
        assert r >= 0.9, r
        print('DISTJOIN-OK', r, info['supersteps'])
    """)
    assert "DISTJOIN-OK" in out


def test_distributed_join_chunked_dispatch_matches_one_chip_join():
    # verify_batch=2 over 4 shards: at most 8 edges per dispatch, so a
    # window's edges span several chunks
    out = _run("""
        import jax, numpy as np, tempfile, os
        from repro.core import JoinConfig, build_bucket_graph
        from repro.core.distributed import DistributedJoin
        from repro.core.index import DiskJoinIndex
        from repro.core.types import merge_config
        from repro.data import clustered_vectors, epsilon_for_avg_neighbors
        from repro.store.vector_store import FlatVectorStore

        x = clustered_vectors(3000, 32, seed=4)
        eps = epsilon_for_avg_neighbors(x, 10)
        d = tempfile.mkdtemp()
        store = FlatVectorStore.from_array(os.path.join(d, 'x.bin'), x)
        cfg = JoinConfig(epsilon=eps, pad_align=64, num_buckets=16,
                         memory_budget_bytes=x.nbytes // 4,
                         compute_mode='device', verify_batch=2)
        with DiskJoinIndex.build(store, cfg, os.path.join(d, 'i')) as index:
            one = index.self_join()
            flat = merge_config(index.build_config, index.query_defaults)
            graph = build_bucket_graph(index.meta, flat)
            mesh = jax.make_mesh((4,), ('data',))
            pairs, info = DistributedJoin(index.store, index.meta, flat,
                                          mesh=mesh).run(graph)
        assert one.pairs.shape[0] > 0
        assert np.array_equal(pairs, one.pairs)
        assert np.array_equal(info['dists'], one.distances)
        print('CHUNKED-OK', pairs.shape[0], info['supersteps'])
    """, devices=4)
    assert "CHUNKED-OK" in out


def test_chip_smoke_four_chip_rehearsal():
    # the CPU rehearsal of `chip_smoke.py --chips 4`: DistributedJoin on a
    # 4-device data mesh against the one-chip join, and float64 truth
    out = _run("""
        sys.path.insert(0, '.')
        import chip_smoke
        sys.exit(chip_smoke.main(['--rehearse', '--chips', '4']))
    """, devices=4)
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["count"] == 4
    assert "distributed vs one-chip" in out


def test_fsdp_param_sharding_shards_embedding():
    out = _run("""
        import jax, numpy as np
        from repro.configs import get_config, smoke_config
        from repro.models import build_model
        from repro.dist import sharding as shd

        mesh = jax.make_mesh((4, 2), ('data', 'model'))
        cfg = smoke_config(get_config('chatglm3-6b'))
        m = build_model(cfg)
        shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
        sh = shd.param_shardings(shapes, mesh, fsdp=True)
        emb = sh['embed']['table']
        spec = emb.spec
        assert 'model' in str(spec) and 'data' in str(spec), spec
        print('FSDP-OK', spec)
    """)
    assert "FSDP-OK" in out
