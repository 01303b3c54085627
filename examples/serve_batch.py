"""Serving example: batched autoregressive decoding with KV/SSM caches for
any assigned architecture (reduced size), including the sliding-window
long-context mode.

  PYTHONPATH=src python examples/serve_batch.py --arch rwkv6-7b
  PYTHONPATH=src python examples/serve_batch.py --arch granite-8b --window 64
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    sys.exit(main())
