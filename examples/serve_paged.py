"""Paged-KV serving with PIM-malloc page management + Pallas paged attention.

    PYTHONPATH=src python examples/serve_paged.py

Thin wrapper over the production driver (launch/serve.py) at smoke scale.
Page extents come from the unified heap API (PagePool -> Table-2 facade ->
heap.step); decode-time page growth routes through a 2-rank ShardedHeap
fleet (the shard_map tier + FleetRouter accounting); the attention impl is
threaded through ArchConfig.attend_impl (no module globals).
"""
import sys

from repro.launch import serve
from repro.runtime.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    sys.argv = [sys.argv[0], "--arch", "granite_3_8b", "--reduced",
                "--batch", "4", "--prompt-len", "32", "--decode-steps", "48",
                "--impl", "kernel", "--fleet-ranks", "2"]
    serve.main()
