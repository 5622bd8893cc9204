from cocoa_tpu.data.libsvm import (  # noqa: F401
    load_libsvm,
    load_libsvm_range,
    LibsvmData,
)
from cocoa_tpu.data.sharding import (  # noqa: F401
    ShardedDataset,
    resolve_layout,
    resolve_layout_stats,
    shard_dataset,
)
from cocoa_tpu.data.hybrid import resolve_hot_cols  # noqa: F401
from cocoa_tpu.data.ingest import (  # noqa: F401
    IngestIndex,
    IngestReport,
    build_index,
    resolve_ingest_mode,
    stream_shard_dataset,
)
from cocoa_tpu.data.slab_cache import SlabCache  # noqa: F401
from cocoa_tpu.data.columns import (  # noqa: F401
    shard_columns, shard_dense_columns,
)
from cocoa_tpu.data.fleet import (  # noqa: F401
    FleetDataset,
    TenantSpec,
    build_fleet,
    load_fleet_manifest,
    synth_fleet_specs,
    write_fleet_manifest,
)
from cocoa_tpu.data.synth import (  # noqa: F401
    synth_dense,
    synth_dense_sharded,
    synth_sparse,
    write_libsvm,
)
