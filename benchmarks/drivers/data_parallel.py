"""Driver of ``CompiledProgram.with_data_parallel`` over a mesh of chips in
one process. The program is built at the per-replica batch and fed the global
one, which the engine's ``shard_map`` slices over the mesh; the fetched loss
holds one per-replica mean for each chip."""
from __future__ import annotations

from .static_executor import Driver as StaticDriver


class Driver(StaticDriver):
    def compiled(self):
        import paddle_tpu as fluid
        from paddle_tpu.parallel.mesh_utils import make_mesh

        n = self.traffic["replicas"]
        self.mesh = make_mesh([n], ["dp"], list(self.devices)[:n])
        return fluid.CompiledProgram(self.built["main"]).with_data_parallel(
            loss_name=self.built["loss"].name, places=self.mesh)

    def stage(self, array):
        """The global batch laid out over the mesh as the step slices it, so
        that no step moves it between chips."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(array,
                              NamedSharding(self.mesh, PartitionSpec("dp")))
