"""`mfu.decode` in the hybrid expert cell, where the end-to-end metric it
moves is `output_tokens_per_s`."""
import os

from bench.harness import metric_reader

read = metric_reader(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "mfu.decode")
