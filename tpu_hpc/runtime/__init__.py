from tpu_hpc.runtime.distributed import (  # noqa: F401
    HostInfo,
    cleanup_distributed,
    compile_cache_dir,
    get_host_info,
    init_distributed,
    is_main_host,
    print_host0,
    require_accelerator,
)
from tpu_hpc.runtime.mesh import (  # noqa: F401
    MeshSpec,
    build_hybrid_mesh,
    build_mesh,
    local_batch_size,
    named_sharding,
    slice_groups,
    two_tier_spec,
)
from tpu_hpc.runtime.topology import device_summary, topology_report  # noqa: F401
