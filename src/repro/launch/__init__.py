"""Launch helpers: the device mesh the trainer and the benchmark build."""
