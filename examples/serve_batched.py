"""Example: batched serving (continuous batching) of an assigned arch.

  PYTHONPATH=src python examples/serve_batched.py [--arch mamba2-130m]

Prefills a wave of synthetic prompts into fixed batch slots, decodes them
together step by step (greedy), and reports token throughput — the serving
path whose full-scale layouts are proven by the decode_32k / long_500k
dry-run cells.
"""
import sys

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    enable_compile_cache()
    argv = sys.argv[1:]
    if not any(a.startswith("--arch") for a in argv):
        argv = ["--arch", "mamba2-130m"] + argv
    sys.exit(serve_main(argv))
