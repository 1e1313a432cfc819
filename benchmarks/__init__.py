"""Benchmarks: paper tables/figures and serving runs
(``python -m benchmarks.run``, ``python -m benchmarks.<name>``).  Every
benchmark entry point imports this package first, which turns on the
persistent compilation cache (repro/launch/compile_cache.py)."""
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()
