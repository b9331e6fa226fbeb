"""The scope table of the small copies of both cells, compiled on the CPU:
every phase of a round finds its ops, attention shows in every pass, the
ring's collective-permutes are never counted as on-chip mix work, no
fusion, dot or convolution with a scope-free op_name is left in the scan's
body, and the scopes change nothing the chip runs.  The ring needs four
devices before JAX starts, so each cell is built in a child process.  Then
the readers, by hand-summed values, on two slices of chip traces."""
import json
import os
import re
import subprocess
import sys

import pytest

import faults
from harness import common, readers, scopes

ONE, RING = "olmo1b-pd-p4", "olmo1b-ring4-pd-p4"
CHILD = """
import contextlib, json
import jax
import faults, round_hlo
from harness import scopes
table = scopes.table(faults.tiny(%(cell)r))
scoped = round_hlo.text(%(cell)r)
# the same round with every named scope a no-op: a program without them
jax.named_scope = lambda name: contextlib.nullcontext()
print(json.dumps({"table": table, "same": scoped == round_hlo.text(%(cell)r)}))
"""


def _child(script: str, *args, devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join([
                   common.BENCH, os.path.join(common.BENCH, "tests"),
                   os.path.join(common.ROOT, "src")]))
    res = subprocess.run([sys.executable, "-c", script, *args], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=common.ROOT)
    assert res.returncode == 0, res.stderr[-4000:]
    return res


@pytest.fixture(scope="module")
def builds():
    """Per cell, its scope table and whether the scopes leave the compiled
    round as it is, metadata aside; each in a child process, the ring's
    with four devices."""
    return {cell: json.loads(_child(CHILD % {"cell": cell}, devices=n)
                             .stdout.strip().splitlines()[-1])
            for cell, n in ((ONE, 1), (RING, 4))}


@pytest.fixture(scope="module")
def tables(builds):
    return {cell: {k: tuple(v) for k, v in b["table"].items()}
            for cell, b in builds.items()}


@pytest.mark.parametrize("cell", [ONE, RING])
def test_the_scopes_change_nothing_the_chip_runs(builds, cell):
    assert builds[cell]["same"]


def _phases(table):
    """{phase: [(instr, opcode, op_name)]} of a table."""
    out = {}
    for name, (opcode, op_name) in table.items():
        out.setdefault(scopes.phase(name, opcode, op_name), []).append(
            (name, opcode, op_name))
    return out


@pytest.mark.parametrize("cell,phase", [
    (ONE, "fwd"), (ONE, "bwd"), (ONE, "recompute"), (ONE, "update"),
    (RING, "fwd"), (RING, "bwd"), (RING, "recompute"), (RING, "update"),
    (RING, "mix"), (RING, "collective")])
def test_every_phase_of_a_round_has_ops(tables, cell, phase):
    assert _phases(tables[cell]).get(phase)


def test_the_one_worker_mix_compiles_to_nothing(tables):
    # W = [[1]]: the mix is x·1 in f32 and back to the weights' dtype, which
    # XLA folds away; the one-chip cell has no gossip op at all
    assert not any(scopes.vocabulary().gossip in scopes.segments(o)
                   for _c, o in tables[ONE].values())


@pytest.mark.parametrize("cell", [ONE, RING])
@pytest.mark.parametrize("phase", ["fwd", "bwd", "recompute"])
def test_attention_shows_in_every_pass(tables, cell, phase):
    assert any(scopes.is_attention(n, c, o)
               for n, c, o in _phases(tables[cell]).get(phase, []))


def test_the_ring_collective_permutes_are_collective_never_mix(tables):
    phases = _phases(tables[RING])
    permutes = [n for n, (c, _o) in tables[RING].items()
                if c.startswith("collective-permute")]
    assert permutes
    assert {n for n, _c, _o in phases.get("collective", [])} == set(permutes)
    assert not any(c.startswith("collective-permute")
                   for _n, c, _o in phases.get("mix", []))


@pytest.mark.parametrize("cell", [ONE, RING])
def test_every_fusion_dot_and_convolution_has_a_phase(tables, cell):
    # of the scan body's ops that carry an op_name; ops XLA made without
    # one (the CPU's split dots, the loop's copies), the scan's own counter
    # and exit test, and the zero fills autodiff makes for the layer scan's
    # cotangents (``wrapped_broadcast``) hold no scope and are ``other``
    left = [(n, o) for n, c, o in _phases(tables[cell]).get("other", [])
            if c in ("fusion", "dot", "convolution")
            and "closed_call" in scopes.segments(o)
            and not n.startswith("%wrapped_broadcast")]
    assert not left, left


# ------------------------------------------------------------ the parser
HLO = """HloModule m, is_scheduled=true

%fused_computation.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %mul.1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(r)/while/body/closed_call/grad/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/attn/mul"}
  ROOT %convert.1 = f32[4]{0} convert(%mul.1)
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  %gte.1 = f32[4]{0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[4]{0} dot(%fusion.1, %fusion.1), lhs_contracting_dims={}, rhs_contracting_dims={}
  %add.3 = f32[4]{0} add(%dot.2, %gte.1), metadata={op_name="jit(r)/while/body/closed_call/local_step/add"}
  %fusion.1.remat2 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %add.4 = s32[] add(%gte.0, %gte.0), metadata={op_name="jit(r)/while/body/add"}
  ROOT %tuple.5 = (s32[], f32[4]{0}) tuple(%add.4, %add.3)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %collective-permute.6 = f32[4]{0} collective-permute(%x), channel_id=1, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(r)/gossip/shard_map/ppermute"}
  ROOT %multiply.7 = f32[4]{0} multiply(%collective-permute.6, %x), metadata={op_name="jit(r)/gossip/shard_map/mul"}
}
"""


def test_the_parser_places_each_instruction():
    table = scopes.parse(HLO)
    # the fused computation's own instructions are not ops
    assert "%mul.1" not in table and "%convert.1" not in table
    # a fusion without metadata takes its fused computation's name
    assert scopes.phase("%fusion.1", *table["%fusion.1"]) == "bwd"
    assert scopes.is_attention("%fusion.1", *table["%fusion.1"])
    # a dot XLA made without metadata holds no scope
    assert scopes.phase("%dot.2", *table["%dot.2"]) == "other"
    assert scopes.phase("%add.3", *table["%add.3"]) == "update"
    assert scopes.phase("%fusion.1.remat2 = f32[4]{0} fusion(...)",
                        *table["%fusion.1.remat2"]) == "xla_remat"
    assert table["%collective-permute.6"][0] == "collective-permute"
    assert scopes.phase("%collective-permute.6",
                        *table["%collective-permute.6"]) == "collective"
    assert scopes.phase("%multiply.7", *table["%multiply.7"]) == "mix"


def _renamed(text: str, pairs) -> str:
    """``text`` with its metadata dropped and each name of ``pairs``
    renamed at once."""
    text = re.sub(r",?\s*metadata=\{[^{}]*\}", "", text)
    names = dict(pairs)
    return re.sub(r"%[\w.\-]+", lambda m: names.get(m.group(0), m.group(0)),
                  text)


def test_a_renaming_maps_the_names_one_for_one():
    other = _renamed(HLO, [("%add.3", "%add.30"), ("%add.4", "%add.3")])
    names = scopes.renaming(HLO, other)
    assert names["%add.3"] == "%add.30" and names["%add.4"] == "%add.3"
    assert names["%dot.2"] == "%dot.2"
    rows = scopes.parse(HLO)
    assert {names[n]: r for n, r in rows.items()}["%add.30"] == rows["%add.3"]


@pytest.mark.parametrize("edit", [
    lambda t: t.replace("dot(%fusion.1, %fusion.1)", "dot(%gte.1, %gte.1)"),
    lambda t: _renamed(t, [("%add.4", "%add.3")]),
    lambda t: t.replace("f32[4]{0} multiply(", "f32[4]{0} add(")],
    ids=["operands", "two-names-made-one", "opcode"])
def test_a_renaming_refuses_modules_that_differ_beyond_names(edit):
    assert scopes.renaming(HLO, edit(HLO)) is None


def test_the_scope_names_are_the_programs():
    from repro.core import pdsgdm
    from repro.models import transformer
    names = scopes.vocabulary()
    assert (names.grad, names.local_step, names.gossip) == (
        pdsgdm.SCOPE_GRAD, pdsgdm.SCOPE_LOCAL_STEP, pdsgdm.SCOPE_GOSSIP)
    assert names.attention == set(transformer.ATTENTION_SCOPES)


SHARED_CACHE = """
import contextlib, json, re, sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import faults
from harness import scopes
cell = faults.tiny(%(cell)r)
# the same round built without its scopes fills the cache first
scope = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
ran = scopes.lower(cell).compile().as_text()
jax.named_scope = scope
rows = scopes.table(cell)
print(json.dumps({
    "fwd": sum(scopes.phase(n, *r) == "fwd" for n, r in rows.items()),
    "names": sorted(set(rows) - set(re.findall(r"%%[\\w.\\-]+", ran)))}))
"""


def test_a_cache_entry_built_without_scopes_is_matched_by_name(tmp_path):
    # the compile cache's key leaves metadata out: a parent without scopes
    # and this program share entries, so the run may run the parent's
    # executable; the table then takes its op_names from a fresh compile
    # and its names from the executable that ran
    res = _child(SHARED_CACHE % {"cell": ONE}, str(tmp_path))
    assert "holds no scopes" in res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["fwd"] > 0 and out["names"] == []


def test_scopes_are_path_segments_under_the_autodiff_wrappers():
    segs = scopes.segments("jit(r)/grad/vmap(transpose(jvp(attn)))/x")
    assert {"grad", "attn", "r", "x"} <= segs
    assert "attn" not in scopes.segments("jit(r)/grad/attention_like/x")
    assert scopes.instr_name("%fusion.9 = f32[2]{0} fusion(%a)") == \
        "%fusion.9"


READERS = ("fwd_ms.lm", "bwd_ms.lm", "recompute_ms.lm", "xla_remat_ms.lm",
           "attention_ms.lm", "update_ms.lm", "mix_ms.lm")


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_scopes_reads_nothing(monkeypatch, metric):
    monkeypatch.setattr(scopes, "_TABLES", {})
    monkeypatch.setattr(scopes, "vocabulary", lambda: None)
    rec = {"window": [0.0, 10.0], "host": [], "devices": [
        {"name": "/device:TPU:0", "ops": [["%fusion.1", 0.0, 5.0]]}]}
    ctx = {"cell": faults.tiny(ONE), "trace": rec, "rounds": 1}
    reader = common.load_module("metrics", f"{metric}.py")
    assert reader.UNIT == "ms"
    assert reader.read(ctx) is None


# ------------------------------------------------------------ chip slices
# 75.6 ms recorded on a TPU v5 lite (bench/testdata), with the scope table's
# rows of its ops: the last layer's backward with XLA's remat clones, the
# round's last update, the copies out of and into the round's loop and
# XLA's prefetches (no metadata), the feed's ops (another program: not in
# the table), the next round's first forward ops.  No op overlaps another.
# It was recorded from a build that also scoped the MLP, the embedding and
# the head; the readers read no such scope.
SLICE = os.path.join(common.BENCH, "testdata", "olmo1b-pd-p4.scopes.json")


@pytest.fixture
def chip_slice(monkeypatch):
    with open(SLICE, encoding="utf-8") as f:
        rec = json.load(f)
    rows = {k: tuple(v) for k, v in rec.pop("table").items()}
    monkeypatch.setattr(scopes, "table", lambda cell: rows)
    return {"cell": common.load_cell(ONE), "trace": rec, "rounds": 1}


@pytest.mark.parametrize("metric,ns", [
    # %fusion.537, %broadcast_select_fusion.2, %copy.457, the hoisted
    # attention mask %iota_compare_fusion.6, %fusion.538, .539, .540 and
    # eleven ops of 5 to 666 ns
    ("fwd_ms.lm", 117444.0 + 26633 + 19122 + 13676 + 7055 + 7015 + 6362
     + 1914),
    ("bwd_ms.lm", 5624865.0),
    # the recompute's prefetches into this slice carry no metadata
    ("recompute_ms.lm", 0.0),
    # %fusion.637.remat4, .650.remat2, .642.remat4,
    # %convert_bitcast_fusion.20/.19.remat2, %convolution_convert_fusion
    # .28.remat2 and .29.remat
    ("xla_remat_ms.lm", 833076.0 + 712936 + 882286 + 1076743 + 1074970
     + 178971 + 182485),
    ("attention_ms.lm", 6327009.0),
    # %fusion.554, .555, .556, .557, .563, .560, .558, .559
    ("update_ms.lm", 1406691.0 + 1407423 + 1407593 + 1406938 + 2154245
     + 5627752 + 5623320 + 5624575),
    ("mix_ms.lm", 0.0)])
def test_the_readers_on_a_chip_slice(chip_slice, metric, ns):
    reader = common.load_module("metrics", f"{metric}.py")
    assert reader.read(chip_slice) == pytest.approx(ns * 1e-6)


def test_the_phases_of_a_chip_slice_sum_to_its_compute_time(chip_slice):
    phases = {p: scopes.phase_ms(chip_slice, p) for p in scopes.PHASES}
    # 138 copies and prefetches without metadata, 40090073 ns, and 38 of
    # the feed's ops, which are not in the table, 14822 ns
    assert phases["other"] == pytest.approx((40090073.0 + 14822) * 1e-6)
    assert sum(v for p, v in phases.items() if p != "collective") == \
        pytest.approx(readers.compute_ms_per_round(chip_slice))


# 131 ms of chip 0 recorded on four TPU v5 lite (bench/testdata): the end
# of the first round, with the gossip's collective-permutes and its mix,
# then the feed, whose %copy-start and %copy-done share their names with
# the round's (which carry no metadata: ``other``)
RING_SLICE = os.path.join(common.BENCH, "testdata",
                          "olmo1b-ring4-pd-p4.scopes.json")


@pytest.fixture
def ring_slice(monkeypatch):
    with open(RING_SLICE, encoding="utf-8") as f:
        rec = json.load(f)
    rows = {k: tuple(v) for k, v in rec.pop("table").items()}
    monkeypatch.setattr(scopes, "table", lambda cell: rows)
    return {"cell": common.load_cell(RING), "trace": rec, "rounds": 1}


@pytest.mark.parametrize("metric,ns", [
    # %multiply_convert_fusion .7, .6, .5, .4, .3, .2, .1 and the first
    ("mix_ms.lm", 961911.0 + 961386 + 960990 + 961168 + 1507262 + 3851239
     + 3850676 + 3035435),
    # %fusion.608, .609, .610, .611, .617, .614, .612, .613
    ("update_ms.lm", 1406777.0 + 1407148 + 1406565 + 1406535 + 2156978
     + 5624683 + 5622165 + 5622997),
    # %fusion.718, .719, .616 and %copy.347; the loop around them is no op
    ("bwd_ms.lm", 786663.0 + 825012 + 931807 + 27708),
    # the feed's %copy-start and %copy-done read as the round's: no scope
    ("fwd_ms.lm", 0.0),
    ("xla_remat_ms.lm", 0.0)])
def test_the_readers_on_a_ring_slice(ring_slice, metric, ns):
    reader = common.load_module("metrics", f"{metric}.py")
    assert reader.read(ring_slice) == pytest.approx(ns * 1e-6)


def test_the_mix_and_the_collectives_of_a_ring_slice_are_disjoint(ring_slice):
    phases = {p: scopes.phase_ms(ring_slice, p) for p in scopes.PHASES}
    gossip = common.load_module("metrics", "gossip_ms.ring4.py")
    assert phases["collective"] == pytest.approx(gossip.read(ring_slice))
    assert sum(v for p, v in phases.items() if p != "collective") == \
        pytest.approx(readers.compute_ms_per_round(ring_slice))
