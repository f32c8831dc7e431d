"""Distributed tests on a virtual 8-device CPU mesh (SURVEY.md §4: the TPU analog of
test_dist_base.py localhost multi-process NCCL tests + meta-optimizer graph assertions
-> here, sharding-spec and numeric equivalence assertions)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn
from paddle_tpu.distributed.mesh import build_mesh, mesh_scope


def needs_8(n=8):
    return pytest.mark.skipif(len(jax.devices()) < n, reason="needs 8 devices")


class TestMesh:
    def test_build_default(self):
        m = build_mesh()
        assert m.devices.size == len(jax.devices())
        assert m.axis_names == ("dp",)

    def test_hybrid_mesh(self):
        m = build_mesh((2, 4), ("dp", "mp"))
        assert m.shape["dp"] == 2 and m.shape["mp"] == 4


class TestCollectivesInShardMap:
    def test_psum_allreduce(self):
        from jax import shard_map

        mesh = build_mesh((8,), ("dp",))
        x = jnp.arange(8.0)

        def body(v):
            with dist.spmd_context("dp"):
                t = paddle.to_tensor(v)
                out = dist.all_reduce(t)
                return out._data

        f = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        out = f(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))

    def test_all_gather_and_scatter_reduce(self):
        from jax import shard_map

        mesh = build_mesh((8,), ("dp",))
        x = jnp.arange(8.0).reshape(8, 1)

        def body(v):
            with dist.spmd_context("dp"):
                t = paddle.to_tensor(v)
                g = dist.all_gather(None, t)
                return g._data.reshape(1, -1)

        f = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        out = f(x)
        assert out.shape == (8, 8)
        np.testing.assert_allclose(np.asarray(out)[0], np.arange(8.0))

    def test_ppermute_shift(self):
        from jax import shard_map

        mesh = build_mesh((8,), ("dp",))
        x = jnp.arange(8.0).reshape(8, 1)

        def body(v):
            with dist.spmd_context("dp"):
                return dist.collective.p2p_shift(v, "dp", shift=1)

        f = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        out = np.asarray(f(x)).ravel()
        np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))

    def test_eager_single_process_identity(self):
        t = paddle.to_tensor(np.ones(4, np.float32))
        out = dist.all_reduce(t)
        np.testing.assert_allclose(out.numpy(), np.ones(4))
        dist.barrier()
        assert dist.get_world_size() == 1


class TestSpmdTrainer:
    def _net_and_data(self, din=16, dout=4, n=64):
        rng = np.random.RandomState(0)
        net = nn.Sequential(nn.Linear(din, 32), nn.ReLU(), nn.Linear(32, dout))
        x = rng.randn(n, din).astype(np.float32)
        y = rng.randint(0, dout, n).astype(np.int64)
        return net, x, y

    def test_dp_training_matches_single(self):
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        net, x, y = self._net_and_data()
        init_state = {k: v.numpy().copy() for k, v in net.state_dict().items()}

        # single-device eager reference
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        loss = nn.functional.cross_entropy(net(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        opt.step()
        ref = {k: v.numpy().copy() for k, v in net.state_dict().items()}
        ref_loss = float(loss.numpy())

        # sharded trainer on 8-dev mesh
        net2, _, _ = self._net_and_data()
        net2.set_state_dict(init_state)
        opt2 = paddle.optimizer.SGD(learning_rate=0.1, parameters=net2.parameters())
        mesh = build_mesh((8,), ("dp",))
        trainer = SpmdTrainer(net2, opt2, lambda o, l: nn.functional.cross_entropy(o, l), mesh=mesh)
        loss2 = trainer.train_step(paddle.to_tensor(x), paddle.to_tensor(y))
        np.testing.assert_allclose(float(loss2.numpy()), ref_loss, rtol=1e-4)
        trainer.sync_to_layer()
        for k in ref:
            np.testing.assert_allclose(net2.state_dict()[k].numpy(), ref[k], rtol=1e-4, atol=1e-5)

    def test_sharding_stage2_state_is_sharded(self):
        from paddle_tpu.distributed.spmd import SpmdTrainer

        net = nn.Linear(64, 512)  # weight big enough to shard
        opt = paddle.optimizer.Adam(learning_rate=0.001, parameters=net.parameters())
        mesh = build_mesh((8,), ("dp",))
        trainer = SpmdTrainer(net, opt, lambda o, l: ((o - l) ** 2).mean(),
                              mesh=mesh, sharding_stage=2)
        x = paddle.to_tensor(np.random.rand(16, 64).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(16, 512).astype(np.float32))
        loss = trainer.train_step(x, y)
        assert np.isfinite(float(loss.numpy()))
        m1 = trainer.opt_state["weight"]["moment1"]
        # sharded: each device holds 1/8 of the moment rows
        assert m1.sharding.spec != P() or m1.sharding.is_fully_replicated is False

    def test_stage3_param_sharding(self):
        from paddle_tpu.distributed.spmd import SpmdTrainer

        net = nn.Linear(64, 512)
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=net.parameters())
        mesh = build_mesh((8,), ("dp",))
        trainer = SpmdTrainer(net, opt, lambda o, l: ((o - l) ** 2).mean(),
                              mesh=mesh, sharding_stage=3)
        w = trainer.params["weight"]
        assert not w.sharding.is_fully_replicated
        x = paddle.to_tensor(np.random.rand(16, 64).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(16, 512).astype(np.float32))
        loss1 = float(trainer.train_step(x, y).numpy())
        loss2 = float(trainer.train_step(x, y).numpy())
        assert loss2 < loss1

    def test_gradient_accumulation(self):
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        net = nn.Linear(8, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        mesh = build_mesh((8,), ("dp",))
        trainer = SpmdTrainer(net, opt, lambda o, l: ((o - l) ** 2).mean(),
                              mesh=mesh, accumulate_steps=2)
        x = paddle.to_tensor(np.random.rand(16, 8).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(16, 2).astype(np.float32))
        loss = trainer.train_step(x, y)
        assert np.isfinite(float(loss.numpy()))

    def test_recompute(self):
        from paddle_tpu.distributed.spmd import SpmdTrainer

        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        mesh = build_mesh((8,), ("dp",))
        trainer = SpmdTrainer(net, opt, lambda o, l: ((o - l) ** 2).mean(),
                              mesh=mesh, recompute=True)
        x = paddle.to_tensor(np.random.rand(16, 8).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(16, 2).astype(np.float32))
        assert np.isfinite(float(trainer.train_step(x, y).numpy()))


class TestTensorParallel:
    def test_column_row_parallel_specs(self):
        col = dist.ColumnParallelLinear(16, 32)
        row = dist.RowParallelLinear(32, 16)
        assert col.weight.spmd_spec == P(None, "mp")
        assert row.weight.spmd_spec == P("mp", None)
        emb = dist.VocabParallelEmbedding(100, 16)
        assert emb.weight.spmd_spec == P("mp", None)

    def test_tp_trainer_runs_on_mesh(self):
        from paddle_tpu.distributed.spmd import SpmdTrainer
        from paddle_tpu.distributed.split import collect_spmd_specs

        class TPNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = dist.ColumnParallelLinear(16, 64)
                self.down = dist.RowParallelLinear(64, 16)

            def forward(self, x):
                return self.down(nn.functional.relu(self.up(x)))

        net = TPNet()
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=net.parameters())
        mesh = build_mesh((2, 4), ("dp", "mp"))
        specs = collect_spmd_specs(net)
        assert "up.weight" in specs
        trainer = SpmdTrainer(net, opt, lambda o, l: ((o - l) ** 2).mean(),
                              mesh=mesh, extra_param_specs=specs)
        x = paddle.to_tensor(np.random.rand(8, 16).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(8, 16).astype(np.float32))
        loss = trainer.train_step(x, y)
        assert np.isfinite(float(loss.numpy()))
        assert not trainer.params["up.weight"].sharding.is_fully_replicated


class TestFleet:
    def test_strategy_fields(self):
        s = dist.fleet.DistributedStrategy()
        s.sharding = True
        s.sharding_configs = {"sharding_stage": 3, "gradient_merge_acc_step": 2}
        assert s.sharding_configs.sharding_stage == 3
        s.amp = True
        s.amp_configs = {"init_loss_scaling": 1024.0}
        assert s.amp_configs.init_loss_scaling == 1024.0
        s.recompute = True
        s.pipeline_configs = {"accumulate_steps": 4}
        assert s.pipeline_configs.accumulate_steps == 4

    def test_fleet_init_and_trainer(self):
        strategy = dist.fleet.DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {"sharding_stage": 2}
        dist.fleet.init(is_collective=True, strategy=strategy)
        assert dist.fleet.worker_num() >= 1
        net = nn.Linear(32, 256)
        opt = paddle.optimizer.Adam(learning_rate=0.001, parameters=net.parameters())
        opt = dist.fleet.distributed_optimizer(opt)
        trainer = dist.fleet.build_trainer(net, loss_fn=lambda o, l: ((o - l) ** 2).mean())
        assert trainer.sharding_stage == 2
        x = paddle.to_tensor(np.random.rand(16, 32).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(16, 256).astype(np.float32))
        assert np.isfinite(float(trainer.train_step(x, y).numpy()))

    def test_fleet_dygraph_path(self):
        dist.fleet.init(is_collective=True)
        net = nn.Linear(4, 2)
        model = dist.fleet.distributed_model(net)  # world_size==1: passthrough
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
        fopt = dist.fleet.distributed_optimizer(opt)
        x = paddle.to_tensor(np.random.rand(4, 4).astype(np.float32))
        loss = model(x).sum()
        fopt.minimize(loss)
        assert net.weight.grad is not None


class TestDataParallelEager:
    def test_single_process_passthrough(self):
        net = nn.Linear(4, 2)
        dp = paddle.DataParallel(net)
        x = paddle.to_tensor(np.random.rand(3, 4).astype(np.float32))
        out = dp(x)
        assert out.shape == [3, 2]
        out.sum().backward()
        assert net.weight.grad is not None
        assert len(dp.state_dict()) == len(net.state_dict())


class TestRecomputeOffload:
    def test_remat_offload_trains(self):
        """RecomputeConfig.enable_offload parity. On the CPU test backend the
        offload custom call has no lowering, so the trainer warns and falls
        back to plain recompute; the true offload branch is verified on the
        real TPU chip (pinned_host residuals, loss descends)."""
        import jax as _jax

        from paddle_tpu.distributed.mesh import build_mesh
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=net.parameters())
        mesh = build_mesh((1,), ("dp",), devices=_jax.devices()[:1])
        import warnings as _w

        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            trainer = SpmdTrainer(net, opt, loss_fn=nn.CrossEntropyLoss(),
                                  mesh=mesh, recompute=True, remat_offload=True)
            x = paddle.randn([8, 16])
            y = paddle.to_tensor(np.random.RandomState(0).randint(0, 4, (8,)))
            l0 = float(np.asarray(trainer.train_step(x, y)._data))
            l1 = float(np.asarray(trainer.train_step(x, y)._data))
        assert np.isfinite(l0) and l1 < l0
        # the CPU downgrade is loud, not silent
        assert any("remat_offload ignored" in str(w.message) for w in rec)


class TestDistributedHapi:
    def test_model_fit_jit_on_8dev_mesh(self):
        """dist_hapi parity: Model.fit with the whole-step SpmdTrainer adapter
        over the 8-device dp mesh."""
        from paddle_tpu.distributed.mesh import build_mesh, mesh_scope

        paddle.seed(0)
        rng = np.random.RandomState(3)
        X = rng.randn(64, 8).astype(np.float32)
        Y = rng.randint(0, 3, (64, 1)).astype(np.int64)

        class DS(paddle.io.Dataset):
            def __len__(self):
                return 64

            def __getitem__(self, i):
                return X[i], Y[i]

        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 3))
        model = paddle.Model(net, use_jit=True)
        model.prepare(paddle.optimizer.Adam(learning_rate=3e-2,
                                            parameters=net.parameters()),
                      nn.CrossEntropyLoss(), paddle.metric.Accuracy())
        mesh = build_mesh((8,), ("dp",))
        with mesh_scope(mesh):
            hist = model.fit(DS(), epochs=6, batch_size=32, verbose=0)
        res = model.evaluate(DS(), batch_size=32, verbose=0)
        acc = res["acc"] if isinstance(res, dict) else res[-1]
        acc = float(acc[0] if isinstance(acc, (list, tuple)) else acc)
        assert acc > 0.5


class TestTracedRng:
    def test_dropout_varies_per_step_in_jitted_trainer(self):
        """Dropout inside the compiled step must draw fresh masks per step
        (trace-time keys bake ONE mask into the program)."""
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 32), nn.Dropout(0.5), nn.Linear(32, 2))
        opt = paddle.optimizer.SGD(learning_rate=0.0, parameters=net.parameters())
        mesh = build_mesh((8,), ("dp",))
        tr = SpmdTrainer(net, opt, lambda o, l: ((o - l) ** 2).mean(), mesh=mesh)
        x = paddle.to_tensor(np.random.rand(16, 8).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(16, 2).astype(np.float32))
        # lr=0 -> params frozen; loss differences come from dropout masks only
        l1 = float(tr.train_step(x, y)._data)
        l2 = float(tr.train_step(x, y)._data)
        l3 = float(tr.train_step(x, y)._data)
        assert len({round(l1, 9), round(l2, 9), round(l3, 9)}) > 1, (l1, l2, l3)

    def test_dropout_varies_in_localsgd_step(self):
        """Review r2i: localsgd/dgc paths must thread the per-step rng too."""
        from paddle_tpu.distributed.spmd import SpmdTrainer

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 32), nn.Dropout(0.5), nn.Linear(32, 2))
        opt = paddle.optimizer.SGD(learning_rate=0.0, parameters=net.parameters())
        mesh = build_mesh((8,), ("dp",))
        tr = SpmdTrainer(net, opt, lambda o, l: ((o - l) ** 2).mean(),
                         mesh=mesh, localsgd_k=2)
        x = paddle.to_tensor(np.random.rand(16, 8).astype(np.float32))
        y = paddle.to_tensor(np.random.rand(16, 2).astype(np.float32))
        losses = {round(float(tr.train_step(x, y)._data), 9) for _ in range(3)}
        assert len(losses) > 1, losses
