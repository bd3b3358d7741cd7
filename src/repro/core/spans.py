"""Names of the ``jax.named_scope`` spans that mark the router's layers.

A scope is HLO metadata only: it lands in each instruction's
``metadata={op_name="jit(f)/.../<scope>/..."}`` and changes no fusion and
adds no operation, so the program carries its scopes always and costs
nothing for them.  A profiler trace, joined with those op names, splits
device time by layer (``bench/scopes.py``).  Scopes nest: ``TRIP`` sits
inside ``ADVANCE``, ``INSERT`` inside ``COLLECT``, ``WRAP`` inside
``INSERT``, ``SAMPLE`` inside ``UPDATE``; on the serving path ``MLA``,
``ROUTED`` and ``SHARED`` sit inside ``DECODE``.
"""
from __future__ import annotations

OBS = "step.obs"              # features.build_obs: one observation
POLICY = "step.policy"        # sac.act: HAN + actor forward of a decision
ADVANCE = "engine.advance"    # engine.advance_all, whichever backend runs
TRIP = "engine.trip"          # one trip of advance_shard's lockstep loop
COLLECT = "train.collect"     # make_iteration's collect scan
INSERT = "replay.insert"      # replay.add_batch and its sharded twin
WRAP = "replay.wrap"          # add_batch's write of a block that wraps the ring
SAMPLE = "replay.sample"      # replay.sample and its sharded twin
UPDATE = "sac.update"         # make_iteration's update: sample, loss, Adam, Polyak
DECODE = "serve.decode"       # a served model's decode step over all slots
MLA = "mla.attn"              # latent attention of one layer
ROUTED = "moe.routed"         # the held routed experts of one MoE layer
SHARED = "moe.shared"         # the shared experts of one MoE layer

SCOPES = (OBS, POLICY, ADVANCE, TRIP, COLLECT, INSERT, SAMPLE, UPDATE, WRAP,
          DECODE, MLA, ROUTED, SHARED)
