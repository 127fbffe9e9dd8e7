"""Fleet API, DistributeTranspiler, sharded embedding, Wide&Deep tests.

Contracts: reference test_dist_transpiler.py (transpiled op sequences),
incubate/fleet API surface, and the test_dist_base loss-parity pattern
for the collective fleet on the virtual mesh."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.incubate.fleet.base.role_maker import (Role,
                                                       UserDefinedRoleMaker)


def _simple_net(bs=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[bs, 8], dtype="float32")
        y = fluid.data(name="y", shape=[bs, 1], dtype="float32")
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    return main, startup, loss


class TestDistributeTranspiler:
    def _transpile(self, sync_mode=True):
        main, startup, loss = _simple_net()
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(0.1).minimize(loss)
        t = fluid.DistributeTranspiler()
        t.transpile(trainer_id=0, program=main, startup_program=startup,
                    pservers="ps0:6174,ps1:6174", trainers=2,
                    sync_mode=sync_mode)
        return t, main

    def test_trainer_program_op_sequence(self):
        t, main = self._transpile()
        types = [op.type for op in main.global_block().ops]
        assert "sgd" not in types  # updates moved to the servers
        assert types.count("send") == 2  # w, b grads
        assert types.count("recv") == 2
        assert "send_barrier" in types and "fetch_barrier" in types
        assert types.index("send_barrier") > types.index("send")
        assert types.index("recv") > types.index("send_barrier")
        assert types.index("fetch_barrier") > types.index("recv")

    def test_pserver_program_structure(self):
        t, main = self._transpile()
        eps = ["ps0:6174", "ps1:6174"]
        hosted_counts = 0
        for ep in eps:
            ps = t.get_pserver_program(ep)
            ops = ps.global_block().ops
            assert ops[-1].type == "listen_and_serv"
            n_blocks = len(ops[-1].attrs["optimize_blocks"])
            hosted_counts += n_blocks
            for sub in ops[-1].attrs["optimize_blocks"]:
                assert any(o.type == "sgd" for o in sub.ops)
        assert hosted_counts == 2  # w on one server, b on the other

    def test_emulated_ps_training_decreases_loss(self):
        """Trainer + both pserver programs in one process: the loop
        send->optimize-on-server->recv actually trains."""
        from paddle_tpu.ops.distributed_ops import reset_emulated_servers

        reset_emulated_servers()
        main, startup, loss = _simple_net()
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(0.05).minimize(loss)
        t = fluid.DistributeTranspiler()
        t.transpile(trainer_id=0, program=main, startup_program=startup,
                    pservers="ps0:6174,ps1:6174", trainers=1)
        eps = ["ps0:6174", "ps1:6174"]
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            # start the emulated servers
            for ep in eps:
                psprog = t.get_pserver_program(ep)
                exe.run(t.get_startup_program(ep, psprog))
                exe.run(psprog)
            # trainer side
            exe.run(startup)
            rng = np.random.RandomState(0)
            W = rng.randn(8, 1).astype("float32")
            losses = []
            for i in range(30):
                xb = rng.randn(16, 8).astype("float32")
                (l,) = exe.run(t.get_trainer_program(),
                               feed={"x": xb, "y": xb @ W},
                               fetch_list=[loss])
                losses.append(float(np.asarray(l).ravel()[0]))
        assert losses[-1] < 0.3 * losses[0], (losses[0], losses[-1])

    def test_nccl2_mode_inserts_allreduce(self):
        main, startup, loss = _simple_net()
        with fluid.program_guard(main, startup):
            fluid.optimizer.SGD(0.1).minimize(loss)
        config = fluid.DistributeTranspilerConfig()
        config.mode = "nccl2"
        t = fluid.DistributeTranspiler(config=config)
        t.transpile(trainer_id=0, program=main, trainers=4)
        types = [op.type for op in main.global_block().ops]
        assert "c_allreduce_sum" in types
        assert "send" not in types


class TestCollectiveFleet:
    def test_fleet_trains_on_mesh(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        from paddle_tpu.incubate.fleet.collective import (
            Collective, DistributedStrategy)

        fleet = Collective()
        fleet.init(UserDefinedRoleMaker(current_id=0, role=Role.WORKER,
                                        worker_num=8))
        assert fleet.is_worker() and fleet.worker_num() == 8
        main, startup, loss = _simple_net(bs=32)
        with fluid.program_guard(main, startup):
            opt = fleet.distributed_optimizer(
                fluid.optimizer.SGD(0.1), DistributedStrategy())
            opt.minimize(loss)
        types = [op.type for op in main.global_block().ops]
        assert "c_allreduce_sum" in types
        scope = fluid.Scope()
        rng = np.random.RandomState(1)
        W = rng.randn(8, 1).astype("float32")
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            losses = []
            for i in range(15):
                xb = rng.randn(32, 8).astype("float32")
                (l,) = exe.run(fleet.main_program,
                               feed={"x": xb, "y": xb @ W},
                               fetch_list=[loss])
                losses.append(float(np.mean(np.asarray(l))))
        assert losses[-1] < 0.3 * losses[0], (losses[0], losses[-1])


class TestShardedEmbedding:
    def test_lookup_matches_dense(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        from paddle_tpu.parallel.mesh_utils import make_mesh
        from paddle_tpu.parallel.sharded_embedding import (
            build_sharded_table, sharded_embedding_lookup)

        V, D, N = 21, 5, 16  # vocab not divisible by 8: pad path
        rng = np.random.RandomState(0)
        table = rng.randn(V, D).astype("float32")
        ids = rng.randint(0, V, (N,)).astype("int32")
        mesh = make_mesh([8], ["mp"])
        blocks = build_sharded_table(table, 8)  # [8, per, D]

        def f(local_block, ids):
            return sharded_embedding_lookup(local_block[0], ids, "mp")

        smap = jax.shard_map(f, mesh=mesh,
                             in_specs=(P("mp"), P()), out_specs=P(),
                             check_vma=False)
        out = jax.jit(smap)(jnp.asarray(blocks), jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(out), table[ids], rtol=1e-6)

    def test_lookup_grads_flow_to_shards(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        from paddle_tpu.parallel.mesh_utils import make_mesh
        from paddle_tpu.parallel.sharded_embedding import (
            build_sharded_table, sharded_embedding_lookup)

        V, D = 16, 4
        rng = np.random.RandomState(1)
        table = rng.randn(V, D).astype("float32")
        ids = np.array([3, 3, 10, 15], dtype="int32")
        mesh = make_mesh([8], ["mp"])
        blocks = build_sharded_table(table, 8)

        def loss_fn(blocks3, ids):
            def f(local_block, ids):
                e = sharded_embedding_lookup(local_block[0], ids, "mp")
                return jax.lax.psum(jnp.zeros(()), "mp") + (e ** 2).sum()

            smap = jax.shard_map(f, mesh=mesh,
                                 in_specs=(P("mp"), P()),
                                 out_specs=P(), check_vma=False)
            return smap(blocks3, ids)

        g = jax.jit(jax.grad(loss_fn))(jnp.asarray(blocks),
                                       jnp.asarray(ids))
        g_dense = np.asarray(g).reshape(-1, D)[:V]
        # reference grad of sum(emb^2): 2*emb summed per duplicate id
        ref = np.zeros_like(table)
        for i in ids:
            ref[i] += 2 * table[i]
        np.testing.assert_allclose(g_dense, ref, rtol=1e-5, atol=1e-6)


class TestWideDeep:
    def test_builds_and_trains(self):
        from paddle_tpu import models

        B, S, V = 16, 3, 50
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            dense = fluid.data(name="dense", shape=[B, 8], dtype="float32")
            sparse = fluid.data(name="sparse", shape=[B, S], dtype="int64")
            label = fluid.data(name="label", shape=[B, 1], dtype="int64")
            pred = models.wide_deep(dense, sparse, vocab_size=V)
            loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
            fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
        rng = np.random.RandomState(2)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            losses = []
            for i in range(60):
                d = rng.rand(B, 8).astype("float32")
                s = rng.randint(0, V, (B, S)).astype("int64")
                y = (d[:, :1] > 0.5).astype("int64")
                (l,) = exe.run(main, feed={"dense": d, "sparse": s,
                                           "label": y}, fetch_list=[loss])
                losses.append(float(np.asarray(l).ravel()[0]))
        assert all(np.isfinite(losses))
        assert losses[-1] < 0.8 * losses[0], (losses[0], losses[-1])


class TestLaunch:
    def test_env_contract(self):
        from paddle_tpu.distributed.launch import get_cluster_env

        env = get_cluster_env(["10.0.0.1", "10.0.0.2"], 1, 2, 6170, 1)
        assert env["PADDLE_TRAINER_ID"] == "3"
        assert env["PADDLE_TRAINERS_NUM"] == "4"
        assert env["PADDLE_CURRENT_ENDPOINT"] == "10.0.0.2:6171"
        assert env["JAX_COORDINATOR_ADDRESS"] == "10.0.0.1:6170"
        assert env["JAX_PROCESS_ID"] == "3"

    def test_spawns_workers(self, tmp_path):
        import subprocess
        import sys

        script = tmp_path / "w.py"
        script.write_text(
            "import os; print('R%s' % os.environ['PADDLE_TRAINER_ID'])")
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node=2", str(script)],
            capture_output=True, text=True, timeout=60,
            cwd="/root/repo").stdout
        assert "R0" in out and "R1" in out


class TestSyncBatchNorm:
    def test_sharded_stats_match_global(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        B = 32
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data(name="x", shape=[B, 4, 6, 6], dtype="float32")
            y = fluid.layers.batch_norm(x)
            loss = fluid.layers.mean(fluid.layers.elementwise_mul(y, y))
            fluid.optimizer.SGD(0.0).minimize(loss)
        rng = np.random.RandomState(0)
        xb = (rng.randn(B, 4, 6, 6)
              * np.arange(1, B + 1).reshape(B, 1, 1, 1)).astype("float32")
        bs = fluid.BuildStrategy()
        bs.sync_batch_norm = True
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.TPUPlace())
            exe.run(startup)
            compiled = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, build_strategy=bs)
            (y_dp,) = exe.run(compiled, feed={"x": xb}, fetch_list=[y])
        scope2 = fluid.Scope()
        with fluid.scope_guard(scope2):
            exe2 = fluid.Executor(fluid.TPUPlace())
            exe2.run(startup)
            (y_single,) = exe2.run(main, feed={"x": xb}, fetch_list=[y])
        y_dp2 = np.asarray(y_dp).reshape(-1, 4, 6, 6)[:B]
        np.testing.assert_allclose(y_dp2, np.asarray(y_single),
                                   rtol=2e-4, atol=2e-5)
