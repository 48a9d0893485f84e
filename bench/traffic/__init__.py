"""One module per traffic generator, named by a mix's ``generator`` key;
the mixes themselves are data files under ``bench/mixes``."""
