"""Distribution layer: partition-spec derivation, activation-sharding
constraints and cross-shard collectives for the LM substrate
(``sharding``, ``collectives``), and the shared-nothing data-parallel IGD
blocks behind ``repro.engine.shard`` (``data_parallel``). Import each
submodule by name: the package imports none of them, so the analytics
engine loads ``data_parallel`` alone."""
