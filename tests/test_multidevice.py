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


# Each case is one dispatch of 8 lanes, two a chip, on a 4-device data
# mesh, against ``compact_pairs`` run lane by lane on the d² of
# compute_mode="host"'s program (``verify_edges``, the same
# ``ref.pairwise_l2``):
# - empty_chip: chip 1's lanes pair live rows of buckets ε apart;
# - pad_lane: lane 5 is a pad lane (na = nb = 0), as ``_edge_chunks`` pads;
# - intra_lane: lane 6 verifies a bucket against itself;
# - chip_overflow: chip 3 holds more pairs than ``k_cap``, the others fit.
_POOL_CASES = """
    import json
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compute import compact_pairs
    from repro.core.distributed import (verify_edges, verify_edges_compact,
                                        with_auto_axes)

    mesh = with_auto_axes(jax.make_mesh((4,), ('data',)))
    rng = np.random.default_rng(3)
    W, cap, d, E, L = 6, 16, 4, 8, 2
    slab = rng.normal(size=(W, cap, d)).astype(np.float32)
    slab[4] += 100.0                      # far from every other bucket
    slab[5] *= 0.01                       # every row pair within eps
    eps2 = 2.0
    base = dict(edges=[[0, 1], [1, 2], [2, 3], [3, 0], [0, 2], [1, 3],
                       [2, 0], [3, 1]],
                na=[cap, 11, cap, 9, cap, cap, 13, cap],
                nb=[cap, cap, 10, cap, 12, cap, cap, 7],
                intra=[False] * E)
    cases = {
        'empty_chip': dict(edges={2: [0, 4], 3: [4, 1]}),
        'pad_lane': dict(edges={5: [0, 0]}, na={5: 0}, nb={5: 0}),
        'intra_lane': dict(edges={6: [2, 2]}, intra={6: True}),
        'chip_overflow': dict(edges={6: [5, 5], 7: [5, 5]},
                              intra={6: True}),
    }
    rep, dat = NamedSharding(mesh, P()), NamedSharding(mesh, P('data'))
    for name, change in cases.items():
        arg = {k: list(v) for k, v in base.items()}
        for k, by_lane in change.items():
            for e, v in by_lane.items():
                arg[k][e] = v
        edges = np.asarray(arg['edges'], np.int32)
        na, nb = (np.asarray(arg[k], np.int32) for k in ('na', 'nb'))
        intra = np.asarray(arg['intra'])
        args = (jax.device_put(slab, rep), jax.device_put(edges, dat),
                jax.device_put(na, dat), jax.device_put(nb, dat),
                jax.device_put(intra, dat))
        # the d² of compute_mode="host"'s program, the same ref.pairwise_l2
        d2 = verify_edges(*args[:2], eps2)[2]
        want = [jax.device_get(compact_pairs(
            d2[e:e + 1], d2[e:e + 1] <= eps2, na[e:e + 1], nb[e:e + 1],
            intra[e:e + 1], cap * cap)) for e in range(E)]
        want_n = np.array([int(w[0][0]) for w in want])
        chip_n = want_n.reshape(4, L).sum(axis=1)
        # the capacity the chunk is sent at: fits every chip but the
        # fullest in the overflow case, then the re-send at its total
        fits = int(np.sort(chip_n)[-2 if name == 'chip_overflow' else -1])
        k_cap = fits + 1 + (-(fits + 1) % L)
        if name == 'chip_overflow':
            assert chip_n.max() > k_cap
        hlo = verify_edges_compact.lower(*args, eps2, k_cap,
                                         mesh).compile().as_text()
        collectives = [c for c in ('all-reduce', 'all-gather',
                                   'reduce-scatter', 'collective-permute')
                       if c in hlo]
        def pool_of(out, e):  # lane e's rows, cols, d2 in its chip's pool
            chip = e // L
            off = int(out[0][chip * L:e].sum())
            k = int(out[0][e])
            return [o[chip * L:(chip + 1) * L].ravel()[off:off + k]
                    for o in out[1:]]

        def lane_matches(out, e):
            got = pool_of(out, e)
            ref_lane = [w[0, :want_n[e]] for w in want[e][1:]]
            return bool(int(out[0][e]) == want_n[e]
                        and np.array_equal(got[0], ref_lane[0])
                        and np.array_equal(got[1], ref_lane[1])
                        and np.array_equal(got[2].view(np.uint32),
                                           ref_lane[2].view(np.uint32)))

        sent = [k_cap]
        out = jax.device_get(verify_edges_compact(*args, eps2, k_cap, mesh))
        first_counts = out[0].tolist()
        cut = [int(c) for c in np.flatnonzero(
            out[0].reshape(4, L).sum(axis=1) > k_cap)]
        # before any re-send: the chips that fit are whole, and a cut
        # chip's pool holds the first k_cap of its lanes' pairs
        whole_first = [lane_matches(out, e) for e in range(E)
                       if e // L not in cut]
        prefix_first = [bool(np.array_equal(
            out[1][c * L:(c + 1) * L].ravel(), np.concatenate(
                [want[e][1][0, :want_n[e]]
                 for e in range(c * L, (c + 1) * L)])[:k_cap]))
            for c in cut]
        if cut:
            k_cap = 1 << (int(chip_n.max()) - 1).bit_length()
            k_cap += -k_cap % L
            sent.append(k_cap)
            out = jax.device_get(verify_edges_compact(*args, eps2, k_cap,
                                                      mesh))
        shape = [list(o.shape) for o in out]
        same = [lane_matches(out, e) for e in range(E)]
        print(json.dumps(dict(case=name, same=same, cut=cut, sent=sent,
                              whole_first=whole_first,
                              prefix_first=prefix_first,
                              shape=shape, lanes_n=want_n.tolist(),
                              first_counts=first_counts,
                              collectives=collectives)))
"""


@pytest.fixture(scope="module")
def pooled_dispatches() -> dict:
    out = _run(_POOL_CASES, devices=4)
    return {r["case"]: r for r in map(json.loads, out.strip().splitlines())}


@pytest.mark.parametrize("case", ["empty_chip", "pad_lane", "intra_lane",
                                  "chip_overflow"])
def test_verify_edges_compact_pools_each_chip(pooled_dispatches, case):
    """Each chip compacts its own lanes into one pool: every lane's pairs
    and d² bits equal the per-lane ``compact_pairs`` on the same d², in
    order; a chip past ``k_cap`` keeps exact counts and leaves the other
    chips whole, and the re-sent dispatch is whole; the program exchanges
    nothing between chips."""
    r = pooled_dispatches[case]
    assert r["collectives"] == []
    assert r["first_counts"] == r["lanes_n"]   # exact even past k_cap
    assert all(r["same"]) and all(r["whole_first"]), r
    assert r["shape"] == [[8]] + [[8, r["sent"][-1] // 2]] * 3
    if case == "chip_overflow":
        assert r["cut"] == [3] and len(r["sent"]) == 2
        assert len(r["whole_first"]) == 6 and r["prefix_first"] == [True]
    else:
        assert r["cut"] == [] and len(r["sent"]) == 1
    if case == "empty_chip":
        assert r["lanes_n"][2:4] == [0, 0]
    if case == "pad_lane":
        assert r["lanes_n"][5] == 0
    if case == "intra_lane":
        assert 0 < r["lanes_n"][6] < 16 * 15 // 2


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
