"""Distributed execution on EDST fabrics.

Four modules wire the paper's edge-disjoint-spanning-tree constructions
(:mod:`repro.core`) into runnable JAX:

  * :mod:`repro.dist.sharding`       -- logical axis names -> PartitionSpecs
    (tensor-parallel priority rules + FSDP on the largest divisible dim);
  * :mod:`repro.dist.tree_allreduce` -- the k-tree allreduce executed with
    ``ppermute`` under ``shard_map``, gradient chunks striped across the
    edge-disjoint trees;
  * :mod:`repro.dist.striped`        -- first-class tree_reduce_scatter /
    tree_allgather / striped_allreduce collectives: owner stripes per
    vertex, stripe-sized wires instead of full-chunk hops;
  * :mod:`repro.dist.steps`          -- sharded train steps with selectable
    gradient sync (gspmd | psum_dp | edst), the mesh -> star-product
    decomposition chooser, and the ZeRO-1 path (``zero1=True``:
    reduce-scatter grads -> owner-stripe AdamW -> allgather params);
  * :mod:`repro.dist.pipeline`       -- GPipe microbatch schedule over a
    'stage' mesh axis;
  * :mod:`repro.dist.fault`          -- elastic EDST runtime: precompiled
    degraded/rebuilt schedules per failure class, switched by a traced
    schedule id without retracing.

See README.md in this directory for the data flow.
"""
from . import fault, pipeline, sharding, steps, striped, tree_allreduce

__all__ = ["sharding", "steps", "striped", "tree_allreduce", "pipeline",
           "fault"]
