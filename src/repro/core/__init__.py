"""The paper's primary contribution: FL mechanism (aggregation server /
workers / warehouse / pointers), aggregation algorithms (eqs 2.1-2.7),
worker selection (Algorithms 1 & 2), eq-3.4 time estimation, deterministic
event-driven sync/async runtime, pod-level federated training, the
wire-aware transport layer (codec'd flat-buffer weight exchange with exact
byte accounting), hierarchical multi-server topologies (leaf servers over
disjoint worker pools re-aggregated at a root)."""
from . import (aggregation, estimator, events, federated,
               flatbuf, population, selection, server, server_opt, topology,
               transport, warehouse, worker)
from .experiment import (TABLE_4_1, TABLE_4_2, make_setup, repartition_setup,
                         run_fl, run_sequential_baseline, time_to_accuracy)
