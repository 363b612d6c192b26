from brpc_tpu.parallel.mesh import make_mesh, shard_params, shard_batch  # noqa: F401
from brpc_tpu.parallel.collective_channel import CollectiveChannel  # noqa: F401
from brpc_tpu.parallel.ring import ring_attention, ulysses_attention  # noqa: F401
from brpc_tpu.parallel.pipeline import pipeline_apply  # noqa: F401
