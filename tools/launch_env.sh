#!/usr/bin/env bash
# Tuned process environment for the single-host serving stack.
#
#   tools/launch_env.sh python -m repro.launch.det_serve --workers 2 --shm
#   DET_HOST_DEVICES=4 tools/launch_env.sh python -m benchmarks.perf_serve --workers 2
#
# Two knobs, both no-ops when unavailable so the wrapper is always safe:
#
# * tcmalloc: the serving front and its spawned workers allocate/free
#   large staging buffers on every batch; glibc malloc returns them to
#   the kernel and re-faults the pages.  If a tcmalloc build is present
#   on this host it is LD_PRELOADed (existing LD_PRELOAD preserved);
#   otherwise the stock allocator is used silently.
# * XLA host devices: DET_HOST_DEVICES=N appends
#   --xla_force_host_platform_device_count=N to XLA_FLAGS, carving the
#   CPU into N XLA devices — what the mesh/shard_map paths (and the CI
#   multi-device leg) need on a CPU-only host.
#
# The wrapper only exports environment and execs its argv: it never
# changes what the program computes, only how fast the allocator and
# how many host devices it sees.
set -eu

find_tcmalloc() {
    local cand
    for cand in \
        /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
        /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
        /usr/lib/libtcmalloc_minimal.so.4 \
        /usr/lib/libtcmalloc.so.4 \
        /usr/local/lib/libtcmalloc_minimal.so \
        /opt/conda/lib/libtcmalloc_minimal.so; do
        if [ -e "$cand" ]; then
            printf '%s' "$cand"
            return 0
        fi
    done
    return 1
}

if tcmalloc="$(find_tcmalloc)"; then
    export LD_PRELOAD="${LD_PRELOAD:+${LD_PRELOAD}:}${tcmalloc}"
    # large staging buffers are routine, not leaks — keep tcmalloc quiet
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-1099511627776}"
fi

if [ "${DET_HOST_DEVICES:-0}" -gt 0 ] 2>/dev/null; then
    export XLA_FLAGS="${XLA_FLAGS:+${XLA_FLAGS} }--xla_force_host_platform_device_count=${DET_HOST_DEVICES}"
fi

exec "$@"
