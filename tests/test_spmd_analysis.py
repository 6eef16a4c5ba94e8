"""SPMD sharding analyzer (paddle_tpu.analysis.spmd / analysis.hlo, PTA2xx).

Covers: the HLO collective parser; PTA201/PTA202 on a correctly vs
deliberately mis-sharded GPT-MP layer (column->row MLP) with nonzero
bytes-moved estimates, verdicts computed BEFORE any dispatch; the MULTICHIP
dryrun mesh families (dp×mp, dp×sdp×mp — the pp family cannot SPMD-compile
on the CPU backend, the pre-existing PartitionId limitation) analyzing
error-free through fleet.distributed_step; PTA203 pinning single-host
DecodeEngine decode programs collective-free; PTA204 HBM-budget errors
raised before dispatch under FLAGS_shard_check; PTA205 cross-rank schedule
divergence through TCPStore; PTA206 replicated-param findings; the
shard_tensor spec validation, registry watched flags, run-log/report
integration and the ``--hlo`` CLI.
"""
import json
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.analysis import (
    ProgramAnalysisError,
    ShardCheckOptions,
    analyze_compiled,
    analyze_hlo_text,
    analyze_jit,
    shard_check,
    verify_collective_schedule,
)
from paddle_tpu.analysis import hlo as hlo_mod
from paddle_tpu.analysis import spmd as spmd_mod


def _codes(diags):
    return sorted({d.code for d in diags})


# --------------------------------------------------------------- HLO parser
_FAKE_HLO = """\
HloModule jit__step, entry_computation_layout={()->()}

ENTRY %main.1 (Arg_0.1: f32[8,16]) -> f32[8,16] {
  %Arg_0.1 = f32[8,16]{1,0} parameter(0)
  %all-gather = f32[8,32]{0,1} all-gather(f32[8,16]{0,1} %Arg_0.1), channel_id=1, replica_groups=[2,2]<=[4], dimensions={1}, use_global_device_ids=true, metadata={op_name="jit(f)/jit(main)/dot_general" source_file="/tmp/model.py" source_line=42}
  %all-reduce.7 = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %Arg_0.1), channel_id=2, replica_groups={{0,1},{2,3}}, to_apply=%add.clone
  %cp = f32[4,16]{1,0} collective-permute(f32[4,16]{1,0} %Arg_0.1), channel_id=3, source_target_pairs={{0,1},{1,0}}
  ROOT %copy.9 = f32[8,16]{1,0} copy(f32[8,16]{1,0} %all-reduce.7)
}
"""


def test_hlo_parser_extracts_collectives():
    cols = hlo_mod.parse_collectives(_FAKE_HLO)
    assert [c.kind for c in cols] == ["all-gather", "all-reduce",
                                      "collective-permute"]
    ag, ar, cp = cols
    # iota replica_groups [num_groups,group_size]
    assert (ag.group_size, ag.num_groups) == (2, 2)
    # explicit replica_groups {{0,1},{2,3}}
    assert (ar.group_size, ar.num_groups) == (2, 2)
    assert ag.op_name.endswith("dot_general") and ag.source == "model.py:42"
    assert ag.result_shapes == [("f32", (8, 32))]
    assert ag.result_bytes == 8 * 32 * 4
    # ring accounting: all-gather (g-1)/g * result, all-reduce 2x, permute 1x
    assert hlo_mod.moved_bytes(ag) == int(8 * 32 * 4 * 0.5)
    assert hlo_mod.moved_bytes(ar) == int(2 * 8 * 16 * 4 * 0.5)
    assert hlo_mod.moved_bytes(cp) == 4 * 16 * 4
    assert hlo_mod.collective_counts(cols) == {
        "all-gather": 1, "all-reduce": 1, "collective-permute": 1}
    # fingerprint: stable for identical schedules, different otherwise
    assert hlo_mod.schedule_fingerprint(cols) == hlo_mod.schedule_fingerprint(
        hlo_mod.parse_collectives(_FAKE_HLO))
    assert hlo_mod.schedule_fingerprint(cols[:2]) != hlo_mod.schedule_fingerprint(cols)
    # entry memory floor: the parameter plus the largest single result
    floor = hlo_mod.entry_memory_lower_bound(_FAKE_HLO)
    assert floor >= 8 * 16 * 4 + 8 * 32 * 4


def test_analyze_hlo_text_codes():
    opts = ShardCheckOptions(allgather_warn_bytes=1)
    diags, cols = analyze_hlo_text(_FAKE_HLO, opts, label="fake")
    assert len(cols) == 3
    # the dot_general-forced all-gather is both a full gather and a reshard
    assert "PTA201" in _codes(diags) and "PTA202" in _codes(diags)
    # deliberate ppermute (no contraction op_name) is NOT a PTA202 reshard
    assert not any(d.code == "PTA202" and "collective-permute" in d.message
                   for d in diags)
    # severity tiering: tiny bytes drop to info above a huge floor
    lo, _ = analyze_hlo_text(_FAKE_HLO, ShardCheckOptions(
        allgather_warn_bytes=1 << 30))
    assert all(d.severity == "info" for d in lo if d.code in ("PTA201", "PTA202"))
    # decode rule: ANY collective in a decode program is PTA203
    dd, _ = analyze_hlo_text(_FAKE_HLO, ShardCheckOptions(decode=True))
    assert sum(1 for d in dd if d.code == "PTA203") == 3


# -------------------------------------------------- PTA201/202 mis-sharding
def _mlp_chain():
    """The GPT-MP MLP pattern as a bare fn: x @ w1 (column-parallel) ->
    gelu -> @ w2 (row-parallel), output replicated."""

    def f(x, w1, w2):
        return jax.nn.gelu(x @ w1) @ w2

    x = jnp.ones((8, 16), jnp.float32)
    w1 = jnp.ones((16, 64), jnp.float32)
    w2 = jnp.ones((64, 16), jnp.float32)
    return f, (x, w1, w2)


def _chain_report(w2_spec):
    f, args = _mlp_chain()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    sh = lambda s: NamedSharding(mesh, s)  # noqa: E731
    jf = jax.jit(f, in_shardings=(sh(P()), sh(P(None, "mp")), sh(w2_spec)),
                 out_shardings=sh(P()))
    return analyze_jit(jf, args, label="gpt-mp-mlp",
                       options=ShardCheckOptions(allgather_warn_bytes=1))


def test_correct_gpt_mp_layer_analyzes_clean():
    rep = _chain_report(P("mp", None))
    # row-parallel consumes the column-parallel shard in place: the only
    # collective is the partial-sum all-reduce; no PTA2xx finding at all
    assert rep.counts() == {"all-reduce": 1}
    assert rep.diagnostics == []
    assert rep.fingerprint


def test_mis_sharded_gpt_mp_layer_pta201_pta202():
    """A deliberately mis-sharded GPT-MP layer (second weight column-
    parallel like the first, so the contraction operand arrives sharded
    the wrong way) must produce PTA201 + PTA202 with bytes-moved > 0 —
    computed from the lowered program alone, nothing dispatched."""
    rep = _chain_report(P(None, "mp"))
    codes = _codes(rep.diagnostics)
    assert "PTA201" in codes and "PTA202" in codes
    assert rep.counts().get("all-gather", 0) >= 1
    assert rep.moved_bytes > 0
    for d in rep.diagnostics:
        if d.code == "PTA202":
            assert "dot_general" in d.message
    # verdict is machine-readable: the planner's objective-function record
    js = rep.to_json()
    assert js["reshard_bytes"] == rep.moved_bytes
    assert any(row["kind"] == "all-gather" and row["bytes_moved"] > 0
               for row in js["schedule"])
    json.dumps(js)  # fully serializable


# ----------------------------------------- sharded-embedding exchange pin
def test_sharded_embedding_exchange_pta202_clean():
    """The recsys ``ShardedEmbedding`` exchange on a dp4 CPU mesh:
    fwd + grad carry the deliberate ``all_to_all`` pair(s) — a routed
    exchange, NOT a contraction reshard — so the analyzer must report the
    all-to-alls in the schedule with ZERO PTA202 findings (and no implicit
    full-gather of the table: payloads stay O(batch))."""
    from paddle_tpu.distributed.embedding import sharded_embedding_lookup

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
    V, D, B = 32, 8, 16
    table = jnp.arange(V * D, dtype=jnp.float32).reshape(V, D) / (V * D)
    ids = (jnp.arange(B, dtype=jnp.int32) * 5) % V
    sh = lambda s: NamedSharding(mesh, s)  # noqa: E731

    def loss(t, i):
        out = sharded_embedding_lookup(i, t, mesh, axis="dp")
        return jnp.sum(out * out)

    jf = jax.jit(jax.grad(loss), in_shardings=(sh(P("dp")), sh(P("dp"))),
                 out_shardings=sh(P("dp")))
    rep = analyze_jit(jf, (table, ids), label="sharded-embedding",
                      options=ShardCheckOptions(allgather_warn_bytes=1))
    # id exchange + embedding return (fwd) and the grad push (bwd)
    assert rep.counts().get("all-to-all", 0) >= 3
    assert not any(d.code == "PTA202" for d in rep.diagnostics), \
        [d.message for d in rep.diagnostics if d.code == "PTA202"]
    assert not any(d.code == "PTA201" for d in rep.diagnostics), \
        [d.message for d in rep.diagnostics if d.code == "PTA201"]


# ------------------------------------------- dryrun mesh families via fleet
def _fleet_step(dp, mp, sdp=1, stage=0):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.strategy import DistributedStrategy
    from paddle_tpu.models.gpt import (
        GPTConfig,
        GPTForPretraining,
        GPTPretrainingCriterion,
    )

    paddle.seed(0)
    strat = DistributedStrategy()
    strat.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                            "pp_degree": 1, "sharding_degree": sdp}
    if sdp > 1:
        strat.sharding = True
        strat.sharding_configs = {"sharding_stage": stage}
    fleet.init(is_collective=True, strategy=strat)
    cfg = GPTConfig.tiny()
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = fleet.distributed_step(model, opt, GPTPretrainingCriterion())
    batch = dp * sdp * 2
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, 32)).astype("int32"))
    sharded = fleet.shard_batch(ids)
    b = sharded._value if hasattr(sharded, "_value") else sharded
    return step, ((b,), (b,))


@pytest.mark.parametrize("dp,mp,sdp,stage", [(2, 2, 1, 0), (2, 2, 2, 2)],
                         ids=["dp2xmp2", "dp2xsdp2xmp2-zero2"])
def test_dryrun_mesh_correct_specs_analyze_error_free(dp, mp, sdp, stage):
    """The MULTICHIP dryrun hybrid families (minus pp, which cannot
    SPMD-compile on CPU — pre-existing PartitionId limitation): a correctly
    annotated GPT step analyzes with ZERO PTA2xx errors and no
    spec-mismatch reshard, before anything runs."""
    step, batch = _fleet_step(dp, mp, sdp, stage)
    rep = analyze_jit(step._jit, (step.state, batch),
                      label=f"dp{dp}mp{mp}sdp{sdp}")
    assert rep.kind != "aot-unavailable" and rep.fingerprint
    assert rep.errors == []
    # the annotated step's legitimate mp/dp collectives never register as
    # producer/consumer spec mismatches
    assert "PTA202" not in _codes(rep.diagnostics)
    # grad sync / partial sums are visible in the schedule
    assert rep.counts().get("all-reduce", 0) >= 1


def test_trainstep_explain_analyze_attaches_verdict():
    step, batch = _fleet_step(2, 2)
    step.run_steps([((batch[0][0],), (batch[1][0],))])
    rows = step.explain(analyze=True)
    assert rows and all("spmd" in r for r in rows)
    s = rows[0]["spmd"]
    assert s["fingerprint"] and s["collective_count"] >= 1
    assert s["diagnostics"]["error"] == 0
    # what crosses the wire, per kind and element type: one line of the summary, summing to the whole
    by_type = s["moved_bytes_by_type"]
    assert by_type and all(len(k.split()) == 2 for k in by_type)
    assert sum(by_type.values()) == s["reshard_bytes"]


# What the TPU compiler writes (cut from the four-chip step compiled for a described v5e:2x2): one async all-gather
# spelled three times — in the wrapped computations of its start, of the work it overlaps and of its done — and the
# chip's reduce-scatter as an all-reduce with the dynamic-slice of its result, fused under the name all-reduce-scatter.
_TPU_HLO = """\
HloModule jit__step

%all-reduce-scatter (input: bf16[2048,4096]) -> bf16[1024,4096] {
  %input = bf16[2048,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.103 = bf16[2048,4096]{1,0:T(8,128)(2,1)} all-reduce(%input), channel_id=139, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add.34.clone
  %partition-id.9 = u32[] partition-id()
  ROOT %dynamic-slice.296 = bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)} dynamic-slice(%all-reduce.103, %partition-id.9, %partition-id.9), dynamic_slice_sizes={1024,4096}
}

%fused_computation.882 (param_0.2717: bf16[2,2048,4096]) -> (bf16[2,2048,4096], bf16[4,2048,4096]) {
  %param_0.2717 = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.121 = bf16[4,2048,4096]{2,1,0:T(8,128)(2,1)} all-gather(%param_0.2717), channel_id=24, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(_step)/amp_cast/convert_element_type"}
  ROOT %custom-call.19 = (bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)S(1)}, bf16[4,2048,4096]{2,1,0:T(8,128)(2,1)}) custom-call(%all-gather.121), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.694 (param_0.2721: bf16[2,2048,4096], param_1.3267: bf16[4,2048,4096]) -> (bf16[4,2048,2048], bf16[4,2048,4096]) {
  %param_0.2721 = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.123 = bf16[4,2048,4096]{2,1,0:T(8,128)(2,1)} all-gather(%param_0.2721), channel_id=24, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(_step)/amp_cast/convert_element_type"}
  ROOT %tuple.161 = (bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}, bf16[4,2048,4096]{2,1,0:T(8,128)(2,1)}) tuple(%param_0.2721, %all-gather.123)
}

%fused_computation.884 (param_0.2723: bf16[2,2048,4096]) -> bf16[4,2048,4096] {
  %param_0.2723 = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.125 = bf16[4,2048,4096]{2,1,0:T(8,128)(2,1)} all-gather(%param_0.2723), channel_id=24, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(_step)/amp_cast/convert_element_type"}
  ROOT %custom-call.21 = bf16[4,2048,4096]{2,1,0:T(8,128)(2,1)} custom-call(%param_0.2723, %all-gather.125), custom_call_target="AsyncCollectiveDone"
}

ENTRY %main.1 (Arg_0.1: bf16[2048,4096]) -> bf16[1024,4096] {
  %Arg_0.1 = bf16[2048,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.12 = bf16[2048,4096]{1,0:T(8,128)(2,1)} all-reduce(%Arg_0.1), channel_id=7, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add.34.clone
  ROOT %fusion.7 = bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)} fusion(%all-reduce.12), kind=kCustom, calls=%all-reduce-scatter
}
"""


def test_hlo_parser_reads_the_tpu_compilers_spellings():
    cols = hlo_mod.parse_collectives(_TPU_HLO)
    assert [(c.kind, c.name, c.index) for c in cols] == [
        ("reduce-scatter", "all-reduce.103", 0), ("all-gather", "all-gather.121", 1), ("all-reduce", "all-reduce.12", 2)]
    rs, ag, ar = cols
    # the fused reduce-scatter: from the fusion's operand to its result, half the operand on the wire in a group of 2
    assert rs.operand_shapes == [("bf16", (2048, 4096))] and rs.result_shapes == [("bf16", (1024, 4096))]
    assert hlo_mod.moved_bytes(rs) == 2048 * 4096 * 2 // 2
    # the replica groups themselves, from both spellings and through the iota's transpose: sdp against mp on a 2 x 2
    assert rs.groups == ag.groups == ((0, 2), (1, 3)) and ar.groups == ((0, 1), (2, 3))
    assert (ag.group_size, ag.num_groups) == (2, 2)
    assert ag.op_name.split("/")[1] == "amp_cast"
    assert hlo_mod.moved_bytes_by_type(cols) == {
        "reduce-scatter bf16": 2048 * 4096, "all-gather bf16": 4 * 2048 * 4096, "all-reduce bf16": 2 * 2048 * 4096}
    assert hlo_mod._parse_groups("replica_groups=[2,4]<=[8]") == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert hlo_mod._parse_groups("replica_groups=[4,2]<=[2,2,2]T(2,1,0)") == ((0, 4), (2, 6), (1, 5), (3, 7))


# ----------------------------------------------------------- PTA203 decode
def test_decode_engine_programs_pinned_collective_free():
    """Single-host DecodeEngine: every compiled serving program must be
    collective-free — pinned through the PTA203 rule via
    explain(analyze=True)."""
    from paddle_tpu.inference.engine import DecodeEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, stacked=True)
    model = GPTForPretraining(cfg)
    model.eval()
    eng = DecodeEngine(model, max_batch_slots=2, max_seq_len=32)
    eng.generate(np.array([[1, 2, 3]]), max_new_tokens=4)
    rows = eng.explain(analyze=True)
    assert rows
    for row in rows:
        spmd = row.get("spmd")
        assert spmd is not None, row
        assert spmd["collective_count"] == 0
        assert "PTA203" not in spmd["codes"]


# ------------------------------------------------- PTA204 budget pre-flight
def test_hbm_budget_raises_before_dispatch():
    """FLAGS_shard_check + an undersized FLAGS_hbm_budget_mb: the PTA204
    error aborts BEFORE the executable runs (dispatch counter pinned)."""
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.profiler import counters

    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=m.parameters())
    step = TrainStep(m, opt, nn.MSELoss())
    x = paddle.to_tensor(np.ones((4, 8), "float32"))
    y = paddle.to_tensor(np.ones((4, 4), "float32"))
    before = counters().get("train_step.dispatches", 0)
    paddle.set_flags({"FLAGS_shard_check": True, "FLAGS_hbm_budget_mb": 1e-4})
    try:
        with pytest.raises(ProgramAnalysisError) as ei:
            step(x, y)
    finally:
        paddle.set_flags({"FLAGS_shard_check": False,
                          "FLAGS_hbm_budget_mb": 0.0})
    assert "PTA204" in str(ei.value)
    assert counters().get("train_step.dispatches", 0) == before
    # with a sane budget the same step runs and reports a clean check
    paddle.set_flags({"FLAGS_shard_check": True,
                      "FLAGS_hbm_budget_mb": 4096.0})
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            step(x, y)
    finally:
        paddle.set_flags({"FLAGS_shard_check": False,
                          "FLAGS_hbm_budget_mb": 0.0})
    assert not [i for i in w if "FLAGS_shard_check" in str(i.message)]
    assert counters().get("train_step.dispatches", 0) == before + 1


def test_analyze_compiled_budget_option():
    f, args = _mlp_chain()
    compiled = jax.jit(f).lower(*args).compile()
    rep = analyze_compiled(compiled, label="mlp",
                           options=ShardCheckOptions(hbm_budget_mb=1e-4))
    assert [d.code for d in rep.errors] == ["PTA204"]
    ok = analyze_compiled(compiled, label="mlp",
                          options=ShardCheckOptions(hbm_budget_mb=4096))
    assert ok.errors == []


# ------------------------------------------ PTA205 schedule divergence
def _two_rank_store():
    from paddle_tpu.distributed import TCPStore

    master = TCPStore(is_master=True, world_size=2, timeout=10.0)
    worker = TCPStore(port=master.port, world_size=2, timeout=10.0)
    return master, worker


def test_collective_schedule_divergence_pta205():
    rep = _chain_report(P(None, "mp"))       # has a real schedule
    same = _chain_report(P(None, "mp"))
    other = _chain_report(P("mp", None))     # different schedule
    master, worker = _two_rank_store()

    def publish(store, rank, r, tag):
        # publish the rank's schedule; the peer key may not be there yet
        # (single-threaded test) — the publish itself is what matters
        try:
            return verify_collective_schedule(store, rank, 2, r, tag=tag,
                                              timeout=0.05)
        except TimeoutError:
            return None

    try:
        # consistent ranks: both publish the same fingerprint -> clean
        publish(worker, 1, same, "ok")
        assert verify_collective_schedule(master, 0, 2, rep, tag="ok",
                                          timeout=5.0) == []
        # divergent ranks: the error names the peer and the first position
        publish(worker, 1, other, "bad")
        diags = verify_collective_schedule(master, 0, 2, rep, tag="bad",
                                           timeout=5.0)
        assert [d.code for d in diags] == ["PTA205"]
        assert diags[0].severity == "error"
        assert "rank 1" in diags[0].message and "position" in diags[0].message
    finally:
        worker.close()
        master.close()


def test_schedule_divergence_rank1_side():
    """Rank 1 sees the divergence too (symmetric exchange)."""
    rep = _chain_report(P(None, "mp"))
    other = _chain_report(P("mp", None))
    master, worker = _two_rank_store()
    try:
        try:
            verify_collective_schedule(master, 0, 2, rep, tag="t2",
                                       timeout=0.01)
        except TimeoutError:
            pass  # peer key not there yet — rank 0's own key IS published
        diags = verify_collective_schedule(worker, 1, 2, other, tag="t2",
                                           timeout=5.0)
        assert [d.code for d in diags] == ["PTA205"]
    finally:
        worker.close()
        master.close()


# --------------------------------------------------- PTA206 replicated param
def test_replicated_param_pta206():
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("mp",))
    params = {"big": np.zeros((256, 256), np.float32),
              "small": np.zeros((4,), np.float32),
              "sharded": np.zeros((256, 256), np.float32)}
    shardings = {"big": NamedSharding(mesh, P()),
                 "small": NamedSharding(mesh, P()),
                 "sharded": NamedSharding(mesh, P("mp", None))}
    diags = spmd_mod.analyze_params(
        params, shardings, ShardCheckOptions(replicated_param_bytes=1024))
    assert [d.code for d in diags] == ["PTA206"]
    assert diags[0].var == "big" and "4-device" in diags[0].message
    # above-threshold default: nothing fires for these tiny params
    assert spmd_mod.analyze_params(params, shardings) == []


# -------------------------------------------- satellite: spec validation
def test_shard_tensor_spec_validation():
    from paddle_tpu.distributed import ProcessMesh, ShardingSpecError, shard_tensor
    from paddle_tpu.distributed.auto_parallel import _spec_from_dims_mapping

    pm = ProcessMesh(np.arange(2), dim_names=["mp"])
    w = paddle.to_tensor(np.zeros((8, 4), "float32"), stop_gradient=False)
    # unknown axis name
    with pytest.raises(ShardingSpecError, match="does not exist"):
        shard_tensor(w, pm, shard_spec=[None, "tp"])
    # spec longer than the tensor rank
    with pytest.raises(ShardingSpecError, match="entries but"):
        shard_tensor(w, pm, shard_spec=["mp", None, None])
    # one mesh axis on two dims
    pm2 = ProcessMesh(np.arange(4).reshape(2, 2), dim_names=["dp", "mp"])
    w2 = paddle.to_tensor(np.zeros((8, 4), "float32"), stop_gradient=False)
    with pytest.raises(ShardingSpecError, match="at most one dim"):
        shard_tensor(w2, pm2, shard_spec=["mp", "mp"])
    # dims_mapping: out-of-range mesh dim and double-mapped mesh dim
    with pytest.raises(ShardingSpecError, match="not a valid mesh dim"):
        _spec_from_dims_mapping(pm, [0, 5])
    with pytest.raises(ShardingSpecError, match="two tensor dims"):
        _spec_from_dims_mapping(pm2, [1, 1])
    # rank mismatch through the dist_attr spelling
    with pytest.raises(ShardingSpecError, match="dims"):
        shard_tensor(w, dist_attr={"process_mesh": pm, "dims_mapping": [0]})
    # the valid spellings still work
    out = shard_tensor(w, pm, shard_spec=[None, "mp"])
    assert out.dist_spec == P(None, "mp")


# ------------------------------------- satellite: registry watched flags
def test_registry_watched_flags_reselect():
    """FLAGS_shard_check / FLAGS_hbm_budget_mb are folded into the kernel
    selection-cache key: toggling via set_flags re-runs the predicates with
    no explicit cache clear."""
    from paddle_tpu.framework.flags import flag
    from paddle_tpu.ops import registry

    assert set(registry.WATCHED_FLAGS) == {"FLAGS_shard_check",
                                           "FLAGS_hbm_budget_mb"}
    name = "_spmd_test_kernel"
    registry.define_kernel(name)
    registry.register(name, "checked", lambda x: "checked",
                      available=lambda x: bool(flag("FLAGS_shard_check")))
    registry.register(name, "plain", lambda x: "plain", fallback=True)
    x = jnp.ones((2,))
    try:
        assert registry.select(name, x).name == "plain"
        paddle.set_flags({"FLAGS_shard_check": True})
        assert registry.select(name, x).name == "checked"
        paddle.set_flags({"FLAGS_shard_check": False})
        assert registry.select(name, x).name == "plain"
    finally:
        paddle.set_flags({"FLAGS_shard_check": False})
        registry.clear_cache(name)


# -------------------------------------- observability + report integration
def test_shard_check_runlog_counters_and_report_section():
    from paddle_tpu.observability import metrics, runlog
    from paddle_tpu.observability.__main__ import analyze as report_analyze

    f, args = _mlp_chain()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    sh = lambda s: NamedSharding(mesh, s)  # noqa: E731
    jf = jax.jit(f, in_shardings=(sh(P()), sh(P(None, "mp")), sh(P(None, "mp"))),
                 out_shardings=sh(P()))
    compiled = jf.lower(*args).compile()
    before = metrics.counters("analysis.")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rep = shard_check(compiled, component="test", label="mlp-bad",
                          kind="train",
                          options=ShardCheckOptions(allgather_warn_bytes=1))
    assert [i for i in w if "PTA201" in str(i.message)]
    after = metrics.counters("analysis.")
    assert after["analysis.shard_checks"] == before.get("analysis.shard_checks", 0) + 1
    assert after["analysis.diagnostics"] > before.get("analysis.diagnostics", 0)
    evs = [e for e in runlog.monitor().events("shard_check")
           if e.get("label") == "mlp-bad"]
    assert evs and evs[-1]["reshard_bytes"] == rep.moved_bytes
    assert evs[-1]["collectives"].get("all-gather", 0) >= 1
    # the report CLI renders a sharding section from these events
    a = report_analyze(evs)
    sh_sec = a["sharding"]
    assert sh_sec["programs_checked"] == len(evs)
    assert sh_sec["reshard_bytes_total"] >= rep.moved_bytes
    assert "PTA201" in sh_sec["codes"]


# ------------------------------------------------------------------- CLI
def test_cli_hlo_mode(tmp_path, capsys):
    from paddle_tpu.analysis.__main__ import main

    f, args = _mlp_chain()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    sh = lambda s: NamedSharding(mesh, s)  # noqa: E731
    jf = jax.jit(f, in_shardings=(sh(P()), sh(P(None, "mp")), sh(P(None, "mp"))),
                 out_shardings=sh(P()))
    path = tmp_path / "prog.hlo"
    path.write_text(jf.lower(*args).compile().as_text())
    assert main([str(path), "--hlo"]) == 0
    out = capsys.readouterr().out
    assert "collective(s)" in out and "bytes moved" in out
    # JSON mode round-trips the full report
    assert main([str(path), "--hlo", "--json"]) == 0
    js = json.loads(capsys.readouterr().out)
    assert js["collectives"].get("all-gather", 0) >= 1
    assert js["reshard_bytes"] > 0 and js["fingerprint"]
    assert any(fnd["code"] == "PTA201" for fnd in js["findings"])
    # an undersized budget turns into a PTA204 error exit
    assert main([str(path), "--hlo", "--hbm-budget", "0.0001"]) == 1
    # decode rule via the CLI
    assert main([str(path), "--hlo", "--decode", "--strict"]) == 1
    capsys.readouterr()


# ------------------------------------------------- Engine.prepare preflight
def test_engine_prepare_preflight_verdict():
    from paddle_tpu.distributed import Engine, ProcessMesh, shard_tensor
    from paddle_tpu.static import InputSpec

    pm = ProcessMesh(np.arange(2), dim_names=["mp"])

    def build(w2_spec):
        paddle.seed(3)
        m = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 8))
        shard_tensor(m[0].weight, pm, shard_spec=[None, "mp"])
        shard_tensor(m[2].weight, pm, shard_spec=w2_spec)
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        return Engine(m, loss=nn.MSELoss(), optimizer=opt, process_mesh=pm)

    specs = (InputSpec([None, 8], "float32"), InputSpec([None, 8], "float32"))
    good = build(["mp", None]).prepare(inputs_spec=specs[0],
                                       labels_spec=specs[1], analyze=True)
    assert good.shard_report is not None and good.shard_report.fingerprint
    assert good.shard_report.errors == []
    bad = build([None, "mp"]).prepare(inputs_spec=specs[0],
                                      labels_spec=specs[1], analyze=True)
    # the mis-sharded variant's verdict carries the reshard finding and a
    # different schedule — the planner's comparison signal, pre-dispatch
    assert bad.shard_report.counts().get("all-gather", 0) > \
        good.shard_report.counts().get("all-gather", 0)
    assert bad.shard_report.fingerprint != good.shard_report.fingerprint
