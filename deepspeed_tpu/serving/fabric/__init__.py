"""Fault-tolerant multi-replica serving fabric (ISSUE 9).

The traffic layer over N :class:`~deepspeed_tpu.serving.engine.ServingEngine`
replicas: health-checked least-loaded routing with
per-replica circuit breakers, retry/backoff failover that resumes a
dead replica's in-flight requests on a survivor bit-identically (greedy),
bounded-queue backpressure + priority/deadline load shedding, and an
ElasticAgent-style replica supervisor — all behind the small
:class:`~deepspeed_tpu.serving.fabric.replica.Replica` interface that a
real multi-host transport plugs into later. Chaos seams live in
``deepspeed_tpu/testing/fault_injection.py``.
"""

from deepspeed_tpu.serving.fabric.autoscaler import (ElasticAutoscaler,
                                                     ScaleDecision)
from deepspeed_tpu.serving.fabric.health import CircuitBreaker
from deepspeed_tpu.serving.fabric.replica import (InProcessReplica, Replica,
                                                  ReplicaHealth)
from deepspeed_tpu.serving.fabric.router import FabricRouter
from deepspeed_tpu.serving.fabric.supervisor import ReplicaSupervisor
from deepspeed_tpu.serving.fabric.twin import (TWIN_SLO_CONFIG, TwinReport,
                                               run_twin,
                                               synthetic_tenant_trace)

__all__ = ["CircuitBreaker", "ElasticAutoscaler", "FabricRouter",
           "InProcessReplica", "Replica", "ReplicaHealth",
           "ReplicaSupervisor", "ScaleDecision", "TWIN_SLO_CONFIG",
           "TwinReport", "run_twin", "synthetic_tenant_trace"]
