"""The system under test, driven as its users drive it.

One call is one scenario of a study: ``repro.kvsim.run_scenario`` over the
cell's deployment with telemetry on, ending in the host sync that
``run_scenario``'s ``float()`` results make. The configuration names the
placement policy (a class of ``repro.core.policy`` and its parameters) and
the engine's options (``run_scenario`` keywords such as ``trace_mode`` and
``replay_backend``), so a configuration that runs another backend is a new
file, not an edit here. This is the only module of the benchmark that
imports the program.
"""

from __future__ import annotations

import math

from repro.core import policy as policies
from repro.kvsim import (
    ClusterConfig,
    TelemetryConfig,
    WorkloadConfig,
    run_scenario,
)


def workload_config(config: dict, traffic: dict) -> WorkloadConfig:
    return WorkloadConfig(
        num_requests=int(traffic["requests_per_call"]),
        num_keys=int(config["records"]),
        num_nodes=len(config["rtt_ms"]),
        read_fraction=float(traffic["read_fraction"]),
        skewed=True,
        hot_fraction=float(config["hotspot_data_fraction"]),
        hot_traffic=float(config["hotspot_opn_fraction"]),
        affinity=float(config["affinity"]),
        region_weights=tuple(float(w) for w in config["region_weights"]),
        object_bytes=float(config["record_bytes"]),
        object_bytes_sigma=0.0,
    )


def cluster_config(config: dict) -> ClusterConfig:
    memory = config["replica_memory_bytes"]
    return ClusterConfig(
        num_nodes=len(config["rtt_ms"]),
        rtt=tuple(tuple(float(x) for x in row) for row in config["rtt_ms"]),
        service_ms=float(config["service_ms"]),
        master=int(config["master"]),
        value_bytes=float(config["record_bytes"]),
        key_bytes=float(config["key_bytes"]),
        transfer_ms_per_kb=float(config["transfer_ms_per_kb"]),
        capacity_bytes=math.inf if memory is None else float(memory),
    )


class Program:
    """The cell's deployment, ready to be called with a seed."""

    def __init__(self, config: dict, traffic: dict):
        placement = config["placement"]
        self.policy = getattr(policies, placement["policy"])(
            **placement.get("params", {})
        )
        if self.policy.initial_placement != placement["initial"]:
            raise ValueError(f"{placement['policy']} starts from "
                             f"{self.policy.initial_placement!r}, the "
                             f"configuration states {placement['initial']!r}")
        tel = config["telemetry"]
        self.workload = workload_config(config, traffic)
        self.cluster = cluster_config(config)
        self.engine = dict(config.get("engine", {}))
        self.telemetry = TelemetryConfig(
            num_bins=int(tel["num_bins"]), lo_ms=float(tel["lo_ms"]),
            hi_ms=float(tel["hi_ms"]),
        )
        self.interval = int(traffic["requests_per_sweep"])
        self.num_shards = int(config["num_shards"])

    @property
    def requests_per_call(self) -> int:
        return self.workload.num_requests

    def call(self, seed: int):
        """One scenario: ``(SimResult, SimTrace)`` on the host."""
        return run_scenario(
            self.workload, self.cluster, self.policy, seed=seed,
            daemon_interval=self.interval, telemetry=self.telemetry,
            num_shards=self.num_shards, **self.engine,
        )
