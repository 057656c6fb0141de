"""Unit tests for roofline/hlo_analyzer.py on hand-written HLO text.

The analyzer is the ground truth for every compiled-collective assertion in
the repo (check_tune_costmodel, check_coalesced, repro.analysis collective
audit), so its parsing of the HLO text forms — sync and async collectives,
iota vs explicit replica groups, while-loop trip counts — is pinned here
against tiny hand-written modules with known byte counts.
"""
from repro.roofline.hlo_analyzer import analyze_hlo

SUM = """\
%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}
"""


def test_all_gather_count_and_wire():
    hlo = """\
HloModule m

ENTRY %main (p0: f32[8,32]) -> f32[32,32] {
  %p0 = f32[8,32]{1,0} parameter(0)
  ROOT %ag = f32[32,32]{1,0} all-gather(%p0), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true
}
"""
    coll = analyze_hlo(hlo)["collectives"]
    assert coll["counts"]["all-gather"] == 1
    # ring all-gather: result_bytes * (g-1)/g = 32*32*4 * 3/4
    assert coll["all-gather"] == 3072
    assert coll["total"] == 3072


def test_counts_by_dtype_separates_quantized_payload():
    # one u8 wire-code gather + one f32 metadata gather: the per-dtype
    # launch counts are what the coalesced-wire regressions key on.
    hlo = """\
HloModule m

ENTRY %main (p0: u8[4,64], p1: f32[4,64]) -> f32[16,64] {
  %p0 = u8[4,64]{1,0} parameter(0)
  %p1 = f32[4,64]{1,0} parameter(1)
  %agu = u8[16,64]{1,0} all-gather(%p0), replica_groups=[1,4]<=[4], dimensions={0}
  %agf = f32[16,64]{1,0} all-gather(%p1), replica_groups=[1,4]<=[4], dimensions={0}
  %c = f32[16,64]{1,0} convert(%agu)
  ROOT %r = f32[16,64]{1,0} add(%c, %agf)
}
"""
    coll = analyze_hlo(hlo)["collectives"]
    assert coll["counts"]["all-gather"] == 2
    assert coll["counts_by_dtype"] == {"all-gather:u8": 1, "all-gather:f32": 1}
    # u8: 1024*3/4 = 768; f32: 4096*3/4 = 3072
    assert coll["all-gather"] == 768 + 3072


def test_tpu_tiled_layouts_parse():
    # TPU-compiled text puts tiling parentheses inside the layout braces and
    # /*index=N*/ comments inside tuple types; every instruction must still
    # parse, or a chip step would report no collectives at all.
    hlo = """\
HloModule m

ENTRY %main (p0: u8[1,256]) -> (u8[4,256], s32[]) {
  %p0 = u8[1,256]{1,0:T(4,128)(4,1)S(1)} parameter(0)
  %agu = u8[4,1,256]{2,1,0:T(4,128)(4,1)S(1)} all-gather(%p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true
  %a2a = u8[4,1,256]{2,1,0:T(4,128)(4,1)S(1)} all-to-all(%agu), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
  %r = u8[4,256]{1,0:T(4,128)(4,1)} reshape(%a2a)
  %i = s32[]{:T(128)} constant(0)
  ROOT %t = (u8[4,256]{1,0:T(4,128)(4,1)}, /*index=1*/s32[]{:T(128)}) tuple(%r, %i)
}
"""
    coll = analyze_hlo(hlo)["collectives"]
    assert coll["counts_by_dtype"] == {"all-gather:u8": 1, "all-to-all:u8": 1}
    assert coll["all-gather"] == 1024 * 3 // 4


def test_collective_classification_and_wire_formulas():
    hlo = SUM + """\

ENTRY %main (p0: f32[4,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %ag = f32[16,8]{1,0} all-gather(%p0), replica_groups=[1,4]<=[4], dimensions={0}
  %rs = f32[4,8]{1,0} reduce-scatter(%ag), replica_groups=[1,4]<=[4], dimensions={0}, to_apply=%sum
  %p1 = f32[8,8]{1,0} parameter(1)
  %ar = f32[8,8]{1,0} all-reduce(%p1), replica_groups=[1,4]<=[4], to_apply=%sum
  %a2a = f32[8,8]{1,0} all-to-all(%ar), replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %cp = f32[8,8]{1,0} collective-permute(%a2a), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
}
"""
    coll = analyze_hlo(hlo)["collectives"]
    for kind in ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
                 "collective-permute"):
        assert coll["counts"][kind] == 1, kind
    assert coll["all-gather"] == 512 * 3 // 4          # 384
    assert coll["reduce-scatter"] == 128 * 3           # 384
    assert coll["all-reduce"] == 2 * 256 * 3 // 4      # 384
    assert coll["all-to-all"] == 256 * 3 // 4          # 192
    assert coll["collective-permute"] == 256           # full result bytes
    assert coll["total"] == 384 * 3 + 192 + 256


def test_async_start_done_counted_once():
    # async form: the -start op carries a (operand, result) tuple type; the
    # result buffer is the LAST shape and the -done must not double-count.
    hlo = """\
HloModule m

ENTRY %main (p0: u8[8,32]) -> u8[32,32] {
  %p0 = u8[8,32]{1,0} parameter(0)
  %ags = (u8[8,32]{1,0}, u8[32,32]{1,0}) all-gather-start(%p0), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}
  ROOT %agd = u8[32,32]{1,0} all-gather-done(%ags)
}
"""
    coll = analyze_hlo(hlo)["collectives"]
    assert coll["counts"]["all-gather"] == 1
    assert coll["counts_by_dtype"] == {"all-gather:u8": 1}
    assert coll["all-gather"] == 1024 * 3 // 4


def test_while_trip_count_multiplies_collectives():
    hlo = SUM + """\

%cond (carg: (s32[], f32[8,32])) -> pred[] {
  %carg = (s32[], f32[8,32]) parameter(0)
  %ci = s32[] get-tuple-element(%carg), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%ci, %n), direction=LT
}

%body (arg: (s32[], f32[8,32])) -> (s32[], f32[8,32]) {
  %arg = (s32[], f32[8,32]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,32]{1,0} get-tuple-element(%arg), index=1
  %ar = f32[8,32]{1,0} all-reduce(%x), replica_groups=[1,8]<=[8], to_apply=%sum
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %out = (s32[], f32[8,32]) tuple(%ip, %ar)
}

ENTRY %main (p0: f32[8,32]) -> (s32[], f32[8,32]) {
  %p0 = f32[8,32]{1,0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,32]) tuple(%z, %p0)
  ROOT %w = (s32[], f32[8,32]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
}
"""
    coll = analyze_hlo(hlo)["collectives"]
    assert coll["counts"]["all-reduce"] == 7
    assert coll["counts_by_dtype"] == {"all-reduce:f32": 7}
    per_iter = 2 * (8 * 32 * 4) * 7 // 8
    assert coll["all-reduce"] == 7 * per_iter


def test_multi_mesh_group_forms_and_degenerate_axis():
    # same program gathering over two mesh axes: iota form [groups,size],
    # explicit {{...}} form, and a size-1 axis that must NOT count.
    hlo = """\
HloModule m

ENTRY %main (p0: f32[8,16], p1: f32[4,16]) -> f32[16,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[4,16]{1,0} parameter(1)
  %ag_model = f32[16,16]{1,0} all-gather(%p0), replica_groups=[4,2]<=[8], dimensions={0}
  %ag_data = f32[16,16]{1,0} all-gather(%p1), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %ag_degenerate = f32[8,16]{1,0} all-gather(%p0), replica_groups=[8,1]<=[8], dimensions={0}
  ROOT %r = f32[16,16]{1,0} add(%ag_model, %ag_data)
}
"""
    coll = analyze_hlo(hlo)["collectives"]
    assert coll["counts"]["all-gather"] == 2  # degenerate axis excluded
    # g=2: 1024*1/2 = 512; g=4: 1024*3/4 = 768
    assert coll["all-gather"] == 512 + 768


def test_dot_flops_through_while():
    hlo = """\
HloModule m

%cond (carg: (s32[], f32[8,16])) -> pred[] {
  %carg = (s32[], f32[8,16]) parameter(0)
  %ci = s32[] get-tuple-element(%carg), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%ci, %n), direction=LT
}

%body (arg: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %arg = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%arg), index=1
  %w = f32[16,16]{1,0} constant({...})
  %y = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %out = (s32[], f32[8,16]) tuple(%ip, %y)
}

ENTRY %main (p0: f32[8,16]) -> (s32[], f32[8,16]) {
  %p0 = f32[8,16]{1,0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,16]) tuple(%z, %p0)
  ROOT %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"3"}}
}
"""
    out = analyze_hlo(hlo)
    # 2*M*N*K per dot = 2*8*16*16 = 4096, times 3 trips
    assert out["flops"] == 3 * 2 * 8 * 16 * 16
