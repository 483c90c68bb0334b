"""Distributed tests on the 8-device virtual CPU mesh (SURVEY §4:
distributed-vs-single-card numerical equivalence on one host)."""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as opt
import paddle_tpu.distributed as dist

rng = np.random.RandomState(0)

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 virtual devices")


@needs8
class TestMeshAndShard:
    def test_mesh(self):
        mesh = dist.auto_mesh(dp=2, mp=4)
        assert mesh.shape == [2, 4]
        assert mesh.dim_names == ["dp", "mp"]

    def test_shard_tensor(self):
        mesh = dist.auto_mesh(dp=2, mp=4)
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        s = dist.shard_tensor(x, mesh, [dist.Shard(0), dist.Replicate()])
        np.testing.assert_allclose(s.numpy(), x.numpy())
        assert len(s._data.sharding.device_set) == 8
        # local shard is 1/2 of dim0
        assert s._data.addressable_shards[0].data.shape == (4, 16)

    def test_reshard(self):
        mesh = dist.auto_mesh(dp=2, mp=4)
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        s = dist.shard_tensor(x, mesh, [dist.Shard(0), dist.Shard(1)])
        r = dist.reshard(s, mesh, [dist.Replicate(), dist.Replicate()])
        np.testing.assert_allclose(r.numpy(), x.numpy())
        placements = dist.get_placements(r, mesh)
        assert all(p.is_replicated() for p in placements)

    def test_sharded_math_matches_replicated(self):
        mesh = dist.auto_mesh(dp=8)
        a = rng.randn(16, 32).astype(np.float32)
        b = rng.randn(32, 8).astype(np.float32)
        ta = dist.shard_tensor(paddle.to_tensor(a), mesh, [dist.Shard(0)])
        tb = paddle.to_tensor(b)
        out = paddle.matmul(ta, tb)
        np.testing.assert_allclose(out.numpy(), a @ b, atol=1e-4)

    def test_shard_layer(self):
        mesh = dist.auto_mesh(dp=8)
        lin = nn.Linear(4, 4)
        dist.shard_layer(lin, mesh)
        out = lin(paddle.to_tensor(rng.randn(8, 4).astype(np.float32)))
        assert out.shape == [8, 4]


@needs8
class TestCollectives:
    def test_all_reduce_eager(self):
        mesh = dist.auto_mesh(dp=8)
        x = paddle.to_tensor(np.ones((8, 4), np.float32))
        xs = dist.shard_tensor(x, mesh, [dist.Shard(0)])
        g = dist.new_group(axis_names=("dp",))
        out = dist.all_reduce(xs, group=g)
        # psum over dp of per-shard [1,4] ones = 8x ones in every shard
        np.testing.assert_allclose(out.numpy(), np.full((8, 4), 8.0))

    def test_all_gather_eager(self):
        mesh = dist.auto_mesh(dp=8)
        x = paddle.to_tensor(np.arange(8, dtype=np.float32).reshape(8, 1))
        xs = dist.shard_tensor(x, mesh, [dist.Shard(0)])
        g = dist.new_group(axis_names=("dp",))
        lst = []
        dist.all_gather(lst, xs, group=g)
        assert len(lst) == 8
        np.testing.assert_allclose(lst[3].numpy(), [[3.0]])

    def test_traced_collectives_in_shard_map(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = dist.auto_mesh(dp=8)
        g = dist.new_group(axis_names=("dp",))

        def body(x):
            return dist.all_reduce(x, group=g)

        f = jax.jit(shard_map(body, mesh=mesh.jax_mesh,
                              in_specs=P("dp"), out_specs=P("dp"),
                              check_vma=False))
        out = f(np.ones(8, np.float32))
        np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))


@needs8
class TestTPLayers:
    def _mesh(self):
        from paddle_tpu.distributed.fleet import fleet, DistributedStrategy
        s = DistributedStrategy()
        s.hybrid_configs["mp_degree"] = 4
        s.hybrid_configs["dp_degree"] = 2
        fleet.init(is_collective=True, strategy=s)
        return fleet.get_hybrid_communicate_group()

    def test_column_row_parallel_match_dense(self):
        hcg = self._mesh()
        from paddle_tpu.distributed.fleet import ColumnParallelLinear, \
            RowParallelLinear
        paddle.seed(0)
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 16, input_is_parallel=True)
        x = paddle.to_tensor(rng.randn(4, 16).astype(np.float32))
        out = row(col(x))
        # dense reference with the same (global) weights
        ref = x.numpy() @ col.weight.numpy() + col.bias.numpy()
        ref = ref @ row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(out.numpy(), ref, atol=1e-4)
        # weights really are sharded over mp
        assert "mp" in str(col.weight._data.sharding.spec)

    def test_vocab_parallel_embedding(self):
        hcg = self._mesh()
        from paddle_tpu.distributed.fleet import VocabParallelEmbedding
        emb = VocabParallelEmbedding(64, 16)
        idx = paddle.to_tensor(np.array([[1, 5], [63, 0]]))
        out = emb(idx)
        np.testing.assert_allclose(out.numpy()[0, 0],
                                   emb.weight.numpy()[1], atol=1e-6)

    def test_parallel_cross_entropy(self):
        hcg = self._mesh()
        from paddle_tpu.distributed.fleet import ParallelCrossEntropy
        pce = ParallelCrossEntropy()
        logits = rng.randn(4, 64).astype(np.float32)
        labels = np.array([3, 9, 60, 0])
        loss = pce(paddle.to_tensor(logits), paddle.to_tensor(labels))
        e = np.exp(logits - logits.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        ref = -np.log(p[np.arange(4), labels])
        np.testing.assert_allclose(loss.numpy(), ref, atol=1e-5)


@needs8
class TestDPEquivalence:
    def test_dp_training_matches_single(self):
        """SURVEY §4 key pattern: distributed vs single-card numerical
        equivalence."""
        x = rng.randn(16, 8).astype(np.float32)
        y = rng.randn(16, 4).astype(np.float32)

        def run(distributed):
            paddle.seed(11)
            m = nn.Linear(8, 4)
            o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
            xt = paddle.to_tensor(x)
            if distributed:
                mesh = dist.auto_mesh(dp=8)
                xt = dist.shard_tensor(xt, mesh, [dist.Shard(0)])
                m = dist.DataParallel(m)
            loss = F.mse_loss(m(xt), paddle.to_tensor(y))
            loss.backward()
            o.step()
            inner = m._layers if distributed else m
            return float(loss), inner.weight.numpy()

        l1, w1 = run(False)
        l2, w2 = run(True)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        np.testing.assert_allclose(w1, w2, atol=1e-5)


@needs8
class TestPipeline:
    def test_spmd_pipeline_matches_sequential(self):
        from paddle_tpu.distributed.pipelining import spmd_pipeline
        mesh = dist.auto_mesh(pp=4, dp=2)
        n_stages, d = 4, 16
        ws = rng.randn(n_stages, d, d).astype(np.float32) * 0.1
        bs = rng.randn(n_stages, d).astype(np.float32) * 0.1
        x = rng.randn(6, 4, d).astype(np.float32)  # [M, mb, d]

        def stage_fn(params, h):
            w, b = params
            return jax.numpy.tanh(h @ w + b)

        out = spmd_pipeline(stage_fn, (ws, bs), x, mesh.jax_mesh,
                            axis_name="pp")
        ref = x
        for s in range(n_stages):
            ref = np.tanh(ref @ ws[s] + bs[s])
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)

    def test_spmd_pipeline_grads(self):
        from paddle_tpu.distributed.pipelining import spmd_pipeline
        mesh = dist.auto_mesh(pp=4)
        n_stages, d = 4, 8
        ws = rng.randn(n_stages, d, d).astype(np.float32) * 0.1
        x = rng.randn(4, 2, d).astype(np.float32)

        def loss_fn(w):
            def stage_fn(p, h):
                return jax.numpy.tanh(h @ p)
            out = spmd_pipeline(stage_fn, w, x, mesh.jax_mesh, "pp")
            return jax.numpy.sum(out ** 2)

        g = jax.grad(loss_fn)(ws)

        def ref_loss(w):
            h = x
            for s in range(n_stages):
                h = jax.numpy.tanh(h @ w[s])
            return jax.numpy.sum(h ** 2)

        g_ref = jax.grad(ref_loss)(ws)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=1e-4)


@needs8
class TestRecompute:
    def test_recompute_grads_match(self):
        from paddle_tpu.distributed.fleet import recompute
        paddle.seed(5)
        block = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8))
        x = paddle.to_tensor(rng.randn(4, 8).astype(np.float32),
                             stop_gradient=False)
        out = recompute(block, x)
        out.sum().backward()
        g_ckpt = {n: p.grad.numpy().copy()
                  for n, p in block.named_parameters()}
        xg_ckpt = x.grad.numpy().copy()

        block.clear_gradients()
        x2 = paddle.to_tensor(x.numpy(), stop_gradient=False)
        block(x2).sum().backward()
        for n, p in block.named_parameters():
            np.testing.assert_allclose(g_ckpt[n], p.grad.numpy(), atol=1e-5)
        np.testing.assert_allclose(xg_ckpt, x2.grad.numpy(), atol=1e-5)


@needs8
class TestDistCheckpoint:
    def test_save_load_reshard(self, tmp_path):
        mesh = dist.auto_mesh(dp=2, mp=4)
        w = rng.randn(16, 32).astype(np.float32)
        t = dist.shard_tensor(paddle.to_tensor(w), mesh,
                              [dist.Shard(0), dist.Shard(1)])
        dist.save_state_dict({"w": t}, str(tmp_path))
        # load into a DIFFERENT sharding layout
        mesh2 = dist.auto_mesh(dp=8)
        target = dist.shard_tensor(paddle.zeros([16, 32]), mesh2,
                                   [dist.Shard(1)])
        dist.load_state_dict({"w": target}, str(tmp_path))
        np.testing.assert_allclose(target.numpy(), w)

    def test_load_never_materializes_global_tensor(self, tmp_path):
        """VERDICT r1 item 4: re-shard-on-load must assemble only
        shard-sized slices, never the full global array, so host memory
        is bounded by the local shard bytes
        (reference load_state_dict.py:467)."""
        from paddle_tpu.distributed.checkpoint import save_load as SL

        mesh = dist.auto_mesh(dp=8)
        w = rng.randn(64, 16).astype(np.float32)
        t = dist.shard_tensor(paddle.to_tensor(w), mesh, [dist.Shard(0)])
        dist.save_state_dict({"w": t}, str(tmp_path))

        allocs = []
        orig = SL.np.zeros

        def probe(shape, *a, **k):
            allocs.append(tuple(np.atleast_1d(shape)))
            return orig(shape, *a, **k)

        SL.np.zeros = probe
        try:
            target = dist.shard_tensor(paddle.zeros([64, 16]), mesh,
                                       [dist.Shard(1)])
            dist.load_state_dict({"w": target}, str(tmp_path))
        finally:
            SL.np.zeros = orig
        np.testing.assert_allclose(target.numpy(), w)
        assert allocs, "slice reader never ran"
        biggest = max(int(np.prod(s)) for s in allocs)
        assert biggest <= 64 * 16 // 8, allocs  # one target shard, not 64x16

    def test_two_process_save_load_e2e(self, tmp_path):
        """Launcher-spawned 2-process save (each rank its own shards,
        all-rank barrier before the coordinator merge) then both ranks
        load — catches the r1 coordinator-only-barrier race."""
        import socket
        import subprocess
        import sys
        import textwrap

        ports = []
        for _ in range(2):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])

        worker = tmp_path / "ckpt_worker.py"
        worker.write_text(textwrap.dedent("""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import paddle_tpu as paddle
            import paddle_tpu.distributed as dist
            from paddle_tpu.framework.tensor import Tensor
            from jax.sharding import Mesh, PartitionSpec as P, NamedSharding

            dist.init_parallel_env()
            rank = dist.get_rank()
            ckpt = os.environ["CKPT_DIR"]
            w = np.arange(32, dtype=np.float32).reshape(8, 4)
            mesh = Mesh(np.asarray(jax.devices()), ("dp",))
            arr = jax.device_put(w, NamedSharding(mesh, P("dp")))
            dist.save_state_dict({"w": Tensor(arr)}, ckpt)
            # both ranks immediately load the merged checkpoint; rank 1
            # only succeeds if save's metadata barrier held it back
            tgt = paddle.zeros([8, 4])
            dist.load_state_dict({"w": tgt}, ckpt)
            np.testing.assert_allclose(tgt.numpy(), w)
            print("CKPT_OK", flush=True)
        """))

        from paddle_tpu.distributed.launch import Launcher
        import os as _os
        env = dict(_os.environ)
        env.pop("XLA_FLAGS", None)
        env["CKPT_DIR"] = str(tmp_path / "ckpt")
        env["PADDLE_MASTER_PORT"] = str(ports[1])
        env["PYTHONPATH"] = _os.pathsep.join(
            [_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        code = Launcher([sys.executable, str(worker)], nprocs=2,
                        master=f"127.0.0.1:{ports[0]}",
                        log_dir=str(tmp_path / "logs"), base_env=env).run()
        assert code == 0


@needs8
class TestShardOptimizer:
    def test_stage1_states_sharded(self):
        mesh = dist.auto_mesh(dp=8)
        m = nn.Linear(16, 16)
        o = opt.Adam(parameters=m.parameters())
        o = dist.shard_optimizer(o, dist.ShardingStage1(sharding_mesh_dim="dp"))
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        F.mse_loss(m(x), paddle.zeros([8, 16])).backward()
        o.step()
        acc = o._accumulators[m.weight.name]["moment1"]
        assert "dp" in str(acc.sharding.spec)

    def _run_stage(self, stage, seed, steps=3):
        """One model trained `steps` steps under a sharding stage (0 =
        plain Adam).  Returns (losses, weight, optimizer, model)."""
        rng_fixed = np.random.RandomState(seed)
        dist.auto_mesh(dp=8)
        paddle.seed(42)
        m = nn.Linear(16, 16)
        o = opt.Adam(learning_rate=0.1, parameters=m.parameters())
        if stage:
            cfg = {1: dist.ShardingStage1, 2: dist.ShardingStage2,
                   3: dist.ShardingStage3}[stage](sharding_mesh_dim="dp")
            o = dist.shard_optimizer(o, cfg)
        x = paddle.to_tensor(rng_fixed.randn(8, 16).astype(np.float32))
        y = paddle.to_tensor(rng_fixed.randn(8, 16).astype(np.float32))
        losses = []
        for _ in range(steps):
            loss = F.mse_loss(m(x), y)
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss))
        return losses, m.weight, o, m

    def test_stage2_reduce_scatter_grads_and_replicated_params(self):
        """VERDICT r1 item 7: stage-2 semantics — grads shard over dp
        before the update (the reduce-scatter), updated shards gather
        back into a replicated parameter."""
        ref_losses, ref_w, _, _ = self._run_stage(0, seed=3)
        losses, w, o, m = self._run_stage(2, seed=3)

        # numerics match the unsharded run
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
        np.testing.assert_allclose(w.numpy(), ref_w.numpy(), atol=1e-5)
        # grads entering the update are dp-sharded (reduce-scatter)
        g = o._grad_transform(jax.numpy.ones((16, 16),
                                     jax.numpy.float32))
        assert "dp" in str(g.sharding.spec)
        # params stay replicated at stage 2 (per-device bytes == full)
        shard = w._data.addressable_shards[0]
        assert shard.data.shape == (16, 16)

    def test_stage3_param_shards_and_parity(self):
        """Stage-3: parameters live sharded — per-device param bytes are
        1/dp of the full tensor — with loss parity vs stage 0."""
        ref_losses, ref_w, _, _ = self._run_stage(0, seed=4)
        losses, w, o, m = self._run_stage(3, seed=4)

        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
        np.testing.assert_allclose(w.numpy(), ref_w.numpy(), atol=1e-5)
        # parameter is genuinely sharded: local shard is 1/8 of the rows
        shard = w._data.addressable_shards[0]
        assert np.prod(shard.data.shape) == 16 * 16 // 8, shard.data.shape
        # optimizer state equally sharded
        acc = o._accumulators[m.weight.name]["moment1"]
        assert np.prod(acc.addressable_shards[0].data.shape) == \
            16 * 16 // 8
