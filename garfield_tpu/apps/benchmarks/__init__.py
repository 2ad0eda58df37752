"""Scenario harnesses that drive a host plane end to end: the attack x
defense matrix (``defense_bench``), sharded federated rounds
(``fed_bench``) and the control plane under rolling restarts, partitions
and churn (``soak_bench``). The benchmark is ``benchmark/run.py``."""
