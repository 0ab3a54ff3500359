"""paddle_tpu's on-chip benchmark: see README.md here and BENCHMARK.json at the root."""
