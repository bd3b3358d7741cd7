"""The serve cell's check on the CPU at a small size: the program passes,
the control (the reference with float8 operands) fails, and so does a
served model that leaves out its shared expert or the rope part of its
latent cache, or that decodes one slot at a wrong position."""
import json
import os

import jax.numpy as jnp
import pytest

from bench import check
from bench import run as bench_run
from bench.tests import tiny

CELL = "dsv2lite.serve"
# the published kind at a small size: 1 dense and 2 MoE layers, 4 of 8
# routed experts held, top-3, 1 shared expert
CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "intermediate_size": 96, "moe_intermediate_size": 32,
          "n_routed_experts_published": 8, "n_routed_experts": 4,
          "held_expert_ids": [0, 1, 2, 3], "num_experts_per_tok": 3,
          "n_shared_experts": 1, "num_hidden_layers": 3, "vocab_size": 512}
TRAFFIC = {"slots": 4, "max_len": 128,
           "prompt_tokens": {"median": 24, "sigma": 0.7, "min": 8, "max": 64},
           "output_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 32},
           "warmup_completions": 4, "stream_requests": 256,
           "tracked_slots": 4, "kept_every": 2, "checked_requests": 4}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("serve")))
    tiny.edit(os.path.join(root, "bench", "configs", "dsv2lite.json"), CONFIG)
    tiny.edit(os.path.join(root, "bench", "traffic", "serve-chat.json"),
              TRAFFIC)
    return root


def one_run(root, control=False, trace=0):
    return bench_run.run(["--workload", CELL, "--seed", "2200000007",
                          "--seconds", "0.5", "--trace", str(trace)],
                         root=root, require_tpu=False, control=control)


def test_program_passes_and_control_fails(root, capfd):
    res = one_run(root, control=True, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # on the CPU the readers of the TPU's trace report nothing
    assert {"mfu.serve", "expert_load.serve", "device_idle.serve"} <= set(
        res["metrics"])
    control = {k[len("control."):]: v for k, v in res["control"].items()}
    ok, lines = check.verdict(control, bench_run.resolve(root, CELL)["limits"])
    assert not ok, lines
    # the window's iterations give the simulator's latency gradients
    err = capfd.readouterr().err
    line = [x for x in err.splitlines() if "[bench] calibration" in x][-1]
    fit = json.loads(line[line.index("{"):])
    assert fit["n_decode"] > 0 and fit["n_prefill"] > 0


def no_shared_expert(monkeypatch):
    from repro.models import moe
    real = moe.held_moe_block

    def block(params, x, cfg):
        return real({k: v for k, v in params.items() if k != "shared"}, x, cfg)

    monkeypatch.setattr(moe, "held_moe_block", block)


def no_rope_key(monkeypatch):
    """Decode scores that leave out the cached rope key ``k_pe``."""
    from repro.models import mla
    real = mla.attention_absorbed

    def attend(p, cfg, q_nope, q_pe, c_cache, pe_cache, pos):
        return real(p, cfg, q_nope, q_pe, c_cache, jnp.zeros_like(pe_cache),
                    pos)

    monkeypatch.setattr(mla, "attention_absorbed", attend)


def one_slot_ahead(monkeypatch):
    """The last slot decodes one position past its own: a fault of one
    slot, which only a check over many slots sees."""
    from repro.models import mla
    real = mla.decode_step

    def step(params, cfg, cache, token):
        logits, out = real(params, cfg,
                           dict(cache, pos=cache["pos"].at[-1].add(1)), token)
        return logits, dict(out, pos=out["pos"].at[-1].add(-1))

    monkeypatch.setattr(mla, "decode_step", step)


@pytest.mark.parametrize("fault", [no_shared_expert, no_rope_key,
                                   one_slot_ahead])
def test_fault_fails(root, fault, monkeypatch):
    fault(monkeypatch)
    res = one_run(root)
    assert res["correct"] is False, res["checks"]
