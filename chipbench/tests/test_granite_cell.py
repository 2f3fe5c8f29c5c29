"""The granite-3-8b-l20 configuration and its chat-prefix cell's readers.

The configuration file must pass ``program_config`` as it is. Its chat path
is then driven end to end on the CPU with the file's own multipliers, eps,
norm and head, its widths cut to a tiny size (``stand_in``): prefix hits
occur, the sound program passes the check, and each planted fault fails it.
The int8 control is read on the chip only: at this size its noise seldom
turns a greedy token, and where it does depends on how the open loop
batched. The two readers new with the cell read a synthetic trace and
drive.
"""

import copy
from types import SimpleNamespace

import jax
import pytest

from chipbench import driver as drivermod
from chipbench import model
from chipbench import run as runmod
from chipbench.tests import tiny
from chipbench.tests.test_check import (altered_token, half_batch,
                                        unchanged_state)
from chipbench.trace import Op, Reduction
from chipbench.workload import Req

CELL = "granite-3-8b-l20.chat-prefix"

#: the widths a CPU test holds, as (file key, program override, value)
TINY = [("hidden_size", "d_model", 64), ("intermediate_size", "d_ff", 128),
        ("num_hidden_layers", "n_layers", 2),
        ("num_attention_heads", "n_heads", 4),
        ("num_key_value_heads", "n_kv_heads", 2),
        ("head_dim", "head_dim", 16), ("vocab_size", "vocab_size", 256)]


def stand_in() -> dict:
    """The configuration file with its widths cut to ``TINY``, in float32
    (XLA:CPU cannot run bf16 x bf16 dots)."""
    conf = copy.deepcopy(model.load_config("granite-3-8b-l20"))
    over = conf["program"]["overrides"]
    for key, field, value in TINY:
        conf[key] = over[field] = value
    conf["torch_dtype"] = "float32"
    over.update(dtype="float32", param_dtype="float32", fsdp=False)
    conf["engine"] = {"max_batch": 4, "max_len": 96, "block_size": 8}
    return conf


#: the tiny cells' limit, for logits that come divided by logits_scaling
LIMITS = dict(tiny.LIMITS, max_logit_gap=tiny.LIMITS["max_logit_gap"]
              / model.load_config("granite-3-8b-l20")["logits_scaling"])


def run(monkeypatch, **kw):
    """One CPU run of the chat mix on the stand-in -> (result, drive)."""
    drives = []

    def keep(*a, **k):
        drives.append(real(*a, **k))
        return drives[-1]
    real = drivermod.drive
    monkeypatch.setattr(drivermod, "drive", keep)
    bench = copy.deepcopy(tiny.BENCH)
    bench["workloads"].append({"name": CELL, "config": "granite-3-8b-l20",
                               "traffic": "chat-prefix", "chips": 1})
    out = runmod.run_cell(bench, CELL, 2 ** 33 + 7, 2.0, False,
                          backend="jnp", conf=stand_in(), mix=tiny.CHAT,
                          limits=LIMITS, devices=jax.devices()[:1],
                          peaks=tiny.PEAKS, **kw)
    return out, drives[-1]


def test_config_file_serves_granite_with_its_multipliers():
    cfg = model.program_config(model.load_config("granite-3-8b-l20"))
    assert (cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (20, 32, 8, 128)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling, cfg.norm_eps) \
        == (12.0, 0.0078125, 0.22, 16.0, 1e-5)
    assert not cfg.tie_embeddings


def test_stand_in_chat_hits_the_prefix_cache_and_is_correct(monkeypatch):
    out, d = run(monkeypatch)
    assert out["correct"], out["check"]
    assert d.prefix_hits > 0
    hits = [s for s in d.served if s.stamps and s.handle.cached_len]
    assert hits and all(s.handle.cached_len % 8 == 0 for s in hits)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_token])
def test_stand_in_faults_are_not_correct(monkeypatch, fault):
    out, _ = run(monkeypatch, tamper=fault)
    assert not out["correct"], out["check"]


def _served(prompt_len, cached, stamp):
    handle = SimpleNamespace(output=[1], done=True, cached_len=cached)
    return drivermod.Served(Req(0.0, [1] * prompt_len, 4), 0.0,
                            handle=handle, stamps=[stamp])


def _ctx(served, ops=(), runs=()):
    trace = Reduction((0, 10 ** 9), list(ops), list(runs), [])
    d = drivermod.Drive(0.0, 5.0, served, [], 0, 0)
    return SimpleNamespace(drive=d, trace=trace)


def test_extend_step_ms_is_device_time_per_call():
    read = runmod._metric_reader("extend_step_ms.chat")
    ops = [Op("extend_step", "f", "gemm", "linear", 0, 4_000_000),
           Op("extend_step", "g", "other", "x", 5_000_000, 8_000_000),
           Op("paged_step", "h", "gemm", "linear", 0, 9_000_000)]
    runs = [("extend_step", 0, 4_000_000), ("extend_step", 5_000_000,
                                            8_000_000)]
    assert read(_ctx([], ops, runs)) == pytest.approx(3.5)
    assert read(_ctx([], ops[2:], [])) is None


def test_prefix_cached_share_reads_the_requests_of_the_traced_part():
    read = runmod._metric_reader("prefix_cached_share.chat")
    served = [_served(100, 0, 0.1), _served(300, 256, 0.5),
              _served(100, 64, 2.0)]          # past the 1 s traced part
    assert read(_ctx(served)) == pytest.approx(100.0 * 256 / 400)
    assert read(_ctx([])) is None
    for s in served:
        del s.handle.cached_len                # a program that counts none
    assert read(_ctx(served)) is None
