"""Tiered-KV serving demo: zNUMA bias, slice ownership, QoS migration.

  PYTHONPATH=src python examples/serve_tiered.py
"""
from repro.core import compile_cache
from repro.launch import serve as ls


def main():
    compile_cache.enable()
    # local tier deliberately small -> visible zNUMA spill + mitigation
    ls.main(["--arch", "qwen2-1.5b", "--requests", "10",
             "--max-batch", "3", "--local-pages", "8",
             "--pool-pages", "96", "--page-size", "4", "--pdm", "0.2"])


if __name__ == "__main__":
    main()
