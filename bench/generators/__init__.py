"""Table generators, one module per generator name a configuration file
gives; each defines ``make_table(cfg, seed) -> bench.table.Table``."""
