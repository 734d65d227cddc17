"""``benchmark/trace/moe.py``: the dropless MoE block's device time by the
names the program gives its parts, on hand-made events, and on the traces
recorded on the chip from a program that has none of them (``testdata``),
where every reader must return nothing and not raise."""

from __future__ import annotations

import gzip
import os
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402
from benchmark.trace import moe as M              # noqa: E402
from benchmark.trace import reduce as R           # noqa: E402
from benchmark.trace import scopes as S           # noqa: E402

TESTDATA = Path(R.__file__).resolve().parent / "testdata"
NAMED = TESTDATA / "flagship-tiny-dp2mp2-named.xplane.pb.gz"
METRICS = ("moe_ms_per_step", "moe_expert_matmul_ms_per_step",
           "moe_route_dispatch_ms_per_step", "moe_expert_matmul_roofline")
FWD = "jit(train_step)/jvp()/while/body/closed_call/hvd_mlp/"
BWD = ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
       "rematted_computation/hvd_mlp/")


def grouped(name: str) -> str:
    """What XLA's TPU compiler leaves of a ``lax.ragged_dot``."""
    return (f'%{name} = bf16[131072,1024]{{1,0:T(8,128)(2,1)}} custom-call('
            '%get-tuple-element.4, %x.1, %copy.1), '
            'custom_call_target="tpu_custom_call", '
            'frontend_attributes={ragged_dot_tiling="512,512,512"}')


FUSION = ('%fusion.7 = bf16[131072,2048]{1,0:T(8,128)(2,1)} '
          'fusion(bf16[16384,2048]{1,0} %p.1), kind=kLoop')
FLASH = ('%hvd_flash_fwd.2 = (bf16[4,16,4096,128]{3,2,1,0}) custom-call('
         '%q), custom_call_target="tpu_custom_call"')
WHILE = ('%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), '
         'condition=%cond, body=%body')


@pytest.mark.parametrize("text, op_name, part", [
    (FUSION, FWD + "hvd_moe_route/jit(argsort)/sort", "hvd_moe_route"),
    (FUSION, BWD + "hvd_moe_dispatch/gather", "hvd_moe_dispatch"),
    (FUSION, FWD + "hvd_moe_experts/convert_element_type",
     "hvd_moe_experts"),
    # The grouped matmul's kernels: XLA overwrote the path.
    (grouped("ragged-dot-none.11"), "ragged-dot-none", "hvd_moe_experts"),
    (grouped("ragged-dot-metadata"), "ragged-dot-metadata",
     "hvd_moe_experts"),
    (FUSION, FWD + "mul", None),                  # the block's own norm
    (FLASH, "jit(train_step)/jvp()/hvd_attn/pallas_call", None),
    (FUSION, "jit(step)/not_hvd_moe_route/mul", None),   # a token, whole
    (FUSION, "", None),
])
def test_part_of_an_event(text, op_name, part):
    assert M.part_of(text, op_name) == part


def test_classify_device_sorts_self_time_inside_the_whole_steps():
    """Two whole steps of 100 ns; a while spans each step's operations and
    is not work; the grouped matmul counts for the experts and the block
    though it carries no scope; what lies outside the steps is cut."""
    meta = {1: (WHILE, ""), 2: (FUSION, FWD + "hvd_moe_route/top_k"),
            3: (FUSION, FWD + "hvd_moe_dispatch/gather"),
            4: (grouped("ragged-dot-none.2"), "ragged-dot-none"),
            5: (FUSION, BWD + "hvd_moe_experts/mul"),
            6: (FUSION, FWD + "mul"),
            7: (FLASH, "jit(train_step)/jvp()/hvd_attn/pallas_call")}
    ops = []
    for t0 in (1000, 1100):
        ops += [(1, t0, t0 + 90), (2, t0 + 5, t0 + 15), (3, t0 + 15, t0 + 20),
                (4, t0 + 20, t0 + 50), (5, t0 + 50, t0 + 58),
                (6, t0 + 58, t0 + 60), (7, t0 + 60, t0 + 85)]
    ops.append((4, 900, 990))                       # before the first step
    lines = {R.OPS_LINE: ops,
             R.STEPS_LINE: [("s", 1000, 1100), ("s", 1100, 1200)]}
    d = M.classify_device(lines, meta)
    assert dict(d["part_ns"]) == {"hvd_moe_route": 20, "hvd_moe_dispatch": 10,
                                  "hvd_moe_experts": 76}
    assert d["grouped_matmul_ns"] == 60
    assert d["block_ns"] == 20 + 10 + 76 + 4
    assert M.classify_device({}, {}) == {
        "part_ns": {}, "block_ns": 0, "grouped_matmul_ns": 0}


def fake_layers(tmp_path, monkeypatch, recorded):
    """A traced run's ``layers`` over a recorded trace: the file under the
    runner's trace directory, as new as this process."""
    train = loader.load_code("runners", "train")
    monkeypatch.setattr(train, "TRACE_DIR", tmp_path)
    target = tmp_path / "cell" / "plugins" / "profile" / "t" / "x.xplane.pb"
    target.parent.mkdir(parents=True)
    target.write_bytes(gzip.decompress(recorded.read_bytes()))
    os.utime(target, (time.time(), time.time()))
    S._classified.cache_clear()
    M._classified.cache_clear()
    return {"trace": R.reduce_trace(str(target)),
            "attention": {"flops": 1e12, "bytes": 1e9,
                          "moe_expert_matmul": {"flops": 1e12, "bytes": 1e9}},
            "peaks": loader.load_peaks("TPU v5 lite")}


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_names_gives_no_value(
        tmp_path, monkeypatch, name):
    """The parent's program on a traced run of any cell: no ``hvd_moe_*``
    scope, no grouped matmul.  The readers find the trace, agree with
    ``scopes.py`` on its devices and steps, and return nothing."""
    layers = fake_layers(tmp_path, monkeypatch, NAMED)
    out = M.classified(layers)
    assert out is not None and sorted(out["devices"]) == [0, 1, 2, 3]
    base = S.classified(layers)
    for i, d in out["devices"].items():
        assert d["n_programs"] == base["devices"][i]["n_programs"] > 0
        assert not d["part_ns"] and not d["grouped_matmul_ns"]
        # The flagship has an ``hvd_mlp`` block, a dense one: the block's
        # time is scopes.py's, to the nanosecond.
        assert d["block_ns"] == base["devices"][i]["block_ns"]["hvd_mlp"]
    read = loader.load_code("metrics", name).read
    metric = {"name": name, "better": "lower"}
    if name == "moe_ms_per_step":
        assert read(layers, metric) > 0        # any family's MLP block
    else:
        assert read(layers, metric) is None


@pytest.mark.parametrize("name", METRICS)
def test_an_untraced_run_gives_no_value(name):
    layers = {"trace": None, "attention": None, "peaks": None}
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None
