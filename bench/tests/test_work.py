"""Work counts and peaks, each against a count made by hand; the dense
reference's counts against the formulas the harness used before they moved
there; and a reference of another architecture read by the same readers."""
import importlib.util
import json

import numpy as np
import pytest

from bench.harness import spec, trace, work
from bench.harness.trace import Event

from .conftest import FIXTURES, ROOT

ref = spec.load_reference("dense_gqa", ROOT)


class D:  # a toy model: 2 layers, d 8, ff 16, 4 heads of 2, 2 kv heads
    layers, d_model, d_ff, heads, kv_heads, head_dim, vocab = 2, 8, 16, 4, 2, 2, 10


def test_linear_and_logit_flops():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 -> 64+32+32+64+384 = 576 MACs
    assert ref.linear_flops_per_token(D) == 2 * 576 * 2
    assert ref.logit_flops(D) == 2 * 8 * 10


def test_contexts_follow_the_segment_spans():
    # a doc token at slot 5 of a doc starting at 3 (no prelude) sees slots 3..5;
    # a tail token at slot 9 sees 0..9
    ctx = work.token_contexts([5, 9], [0, 0], [3, 0])
    assert list(ctx) == [3, 10]


def test_chunk_kernel_counts_each_rows_slots_once():
    # row 0: doc tokens at slots 4,5 (doc starts at 4) and tail tokens 6,7;
    # row 1: one decode token at slot 2
    row_of = [0, 0, 0, 0, 1]
    slots = [4, 5, 6, 7, 2]
    p_end = [0, 0, 0, 0, 0]
    s_start = [4, 4, 0, 0, 0]
    flops, nbytes = ref.chunk_kernel_work(D, 2, 2, row_of, slots, p_end, s_start)
    ctx = 1 + 2 + 7 + 8 + 3
    assert flops == 4 * 4 * 2 * ctx * 2
    kv_slots = 8 + 3                  # row 0 reads 0..7 once, row 1 reads 0..2
    assert work.attended_slots(row_of, slots, p_end, s_start) == kv_slots
    assert nbytes == (kv_slots * 2 * 2 * 2 * 2 + 5 * 4 * 2 * 2 * 2) * 2


def test_decode_kernel_and_step_flops():
    flops, nbytes = ref.decode_kernel_work(D, 1, 2, [3, 5])
    assert flops == 4 * 4 * 2 * 8 * 2
    assert nbytes == (8 * 2 * 2 * 2 * 1 + 2 * 4 * 2 * 2 * 2) * 2
    plan = {"kind": "decode", "ctx": np.array([3, 5]), "sampled": 2}
    assert ref.step_flops(D, plan) == (
        2 * ref.linear_flops_per_token(D) + flops + 2 * ref.logit_flops(D))
    assert ref.step_flops(D, {"kind": "fused", "sampled": 1}) is None
    with pytest.raises(KeyError):
        ref.kernel_work("no_such_kernel", D, {"kv_bytes": 2, "act_bytes": 2}, plan)


def test_min_time_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.min_time(1000.0, 50.0, peaks) == 10.0
    assert work.min_time(100.0, 50.0, peaks) == 5.0


def test_peaks_known_and_unknown_kind():
    p = spec.load_peaks("TPU v5 lite", ROOT)
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99", ROOT)


# ---------------------------------------------------------------------------
# the dense reference's counts are the formulas bench/harness/work.py held
# before they moved into the reference, to the bit
# ---------------------------------------------------------------------------


class Old:
    """The harness's dense GQA formulas as they were, kept verbatim."""

    @staticmethod
    def linear_flops_per_token(d):
        qd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
        per_layer = 2 * (d.d_model * qd + 2 * d.d_model * kvd + qd * d.d_model
                         + 3 * d.d_model * d.d_ff)
        return float(per_layer * d.layers)

    @staticmethod
    def logit_flops(d):
        return 2.0 * d.d_model * d.vocab

    @staticmethod
    def attn_flops(d, ctx):
        return 4.0 * d.heads * d.head_dim * float(np.sum(ctx))

    @staticmethod
    def _union_len(intervals):
        total, end = 0, -1
        for lo, hi in sorted(intervals):
            if hi <= lo:
                continue
            if lo > end:
                total += hi - lo
                end = hi
            elif hi > end:
                total += hi - end
                end = hi
        return total

    @classmethod
    def chunk_kernel_work(cls, d, kv_bytes, act_bytes, row_of, slots, p_end, s_start):
        row_of, slots = np.asarray(row_of), np.asarray(slots)
        p_end, s_start = np.asarray(p_end), np.asarray(s_start)
        flops = cls.attn_flops(d, work.token_contexts(slots, p_end, s_start))
        kv_slots = 0
        for r in np.unique(row_of):
            m = row_of == r
            iv = [(0, int(p)) for p in np.unique(p_end[m])]
            for s in np.unique(s_start[m]):
                iv.append((int(s), int(slots[m][s_start[m] == s].max()) + 1))
            kv_slots += cls._union_len(iv)
        kv = kv_slots * d.kv_heads * d.head_dim * 2 * kv_bytes
        qo = len(slots) * d.heads * d.head_dim * 2 * act_bytes
        return flops * d.layers, float(kv + qo) * d.layers

    @classmethod
    def decode_kernel_work(cls, d, kv_bytes, act_bytes, ctx):
        ctx = np.asarray(ctx, np.int64)
        kv = float(ctx.sum()) * d.kv_heads * d.head_dim * 2 * kv_bytes
        qo = len(ctx) * d.heads * d.head_dim * 2 * act_bytes
        return cls.attn_flops(d, ctx) * d.layers, (kv + qo) * d.layers

    @classmethod
    def step_model_flops(cls, d, plan):
        if plan["kind"] == "ragged":
            ctx = work.token_contexts(plan["slots"], plan["p_end"], plan["s_start"])
        else:
            ctx = np.asarray(plan["ctx"])
        return (len(ctx) * cls.linear_flops_per_token(d) + cls.attn_flops(d, ctx) * d.layers
                + plan["sampled"] * cls.logit_flops(d))


QWEN = ref.Dims.from_config(json.loads(
    (ROOT / "bench" / "configs" / "qwen2.5-3b.json").read_text())["model"])


def ragged_plan(seed: int) -> dict:
    """A packed step as the engine builds one: decode rows of one token,
    and prompt chunks whose tokens are documents (restarting spans after a
    prelude) or tail, at cache slots up to 1024."""
    rng = np.random.default_rng(seed)
    row_of, slots, p_end, s_start = [], [], [], []
    for r in range(int(rng.integers(1, 9))):
        if rng.random() < 0.5:                     # a decode row
            row_of.append(r)
            slots.append(int(rng.integers(0, 1024)))
            p_end.append(0)
            s_start.append(0)
            continue
        start, n = int(rng.integers(0, 700)), int(rng.integers(1, 129))
        prelude = int(rng.integers(0, 64))
        doc = start - int(rng.integers(0, 200)) if rng.random() < 0.6 else None
        for t in range(start, start + n):
            row_of.append(r)
            slots.append(t)
            if doc is not None and doc > prelude:
                p_end.append(prelude)
                s_start.append(doc)
            else:
                p_end.append(0)
                s_start.append(0)
    a = lambda x: np.asarray(x, np.int32)  # noqa: E731
    return {"kind": "ragged", "row_of": a(row_of), "slots": a(slots), "p_end": a(p_end),
            "s_start": a(s_start), "sampled": int(rng.integers(1, 9)), "padded": 0}


def decode_plan(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    ctx = rng.integers(1, 1025, int(rng.integers(1, 33))).astype(np.int32)
    return {"kind": "decode", "ctx": ctx, "sampled": len(ctx), "padded": 32}


PLANS = [ragged_plan(s) for s in range(6)] + [decode_plan(s) for s in range(4)]
CONFIG = json.loads((ROOT / "bench" / "configs" / "qwen2.5-3b.json").read_text())


@pytest.mark.parametrize("plan", PLANS, ids=[f"{p['kind']}{i}" for i, p in enumerate(PLANS)])
def test_dense_counts_equal_the_old_formulas_to_the_bit(plan):
    d, kv, act = QWEN, CONFIG["kv_bytes"], CONFIG["act_bytes"]
    assert ref.step_flops(d, plan) == Old.step_model_flops(d, plan)
    chunk = ref.kernel_work("paged_chunk_attention", d, CONFIG, plan)
    dec = ref.kernel_work("paged_decode_attention", d, CONFIG, plan)
    if plan["kind"] == "ragged":
        assert dec is None
        assert chunk == Old.chunk_kernel_work(d, kv, act, plan["row_of"], plan["slots"],
                                              plan["p_end"], plan["s_start"])
    else:
        assert chunk is None
        assert dec == Old.decode_kernel_work(d, kv, act, plan["ctx"])


# ---------------------------------------------------------------------------
# another architecture's counts, read by the same readers
# ---------------------------------------------------------------------------


def _latent():
    path = FIXTURES / "latent_attention.py"
    sp = importlib.util.spec_from_file_location("bench_fixture_latent_attention", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def _one_device_ctx(reference, dims, plans, us_per_step=100):
    """A traced window in which each plan is one step program run of
    ``us_per_step`` microseconds on one device, holding one kernel call of
    half that."""
    dev = "/device:TPU:0"
    ev = [Event("/host:CPU", "python", "bench:traced", 0, 10**9)]
    t = 1000
    for p in plans:
        mod = ("jit__ragged_step_fn(1)" if p["kind"] == "ragged"
               else "jit__decode_pallas_fn(2)")
        end = t + us_per_step * 1000
        ev += [Event(dev, "XLA Modules", mod, t, end),
               Event(dev, "XLA Ops", "%fusion.1 = bf16[8] fusion(...)", t, t + (end - t) // 2),
               Event(dev, "XLA Ops", "%cc.1 = custom-call(), custom_call_target=\"tpu_custom_call\"",
                     t + (end - t) // 2, end)]
        t = end + 1000
    config = {"kv_bytes": 2, "act_bytes": 2,
              "step_modules": {"ragged": "ragged_step", "decode": "decode_pallas"},
              "kernels": {"paged_chunk_attention": {"module": "ragged_step",
                                                    "op": "tpu_custom_call"},
                          "paged_decode_attention": {"module": "decode_pallas",
                                                     "op": "tpu_custom_call"}}}
    red = trace.reduce(ev, kernels=config["kernels"])
    return {"reduced": red, "plans": plans, "dims": dims, "config": config,
            "reference": reference, "peaks": spec.load_peaks("TPU v5 lite", ROOT),
            "counters": ({}, {})}


def test_latent_attention_counts_read_through_the_harness():
    """DeepSeek-V2-Lite's widths: 16 heads scoring a 576-wide latent key and
    summing a 512-wide latent value, one latent per slot read as both. The
    readers know nothing of it: the fixture reference is all they see."""
    lat = _latent()
    cfg = json.loads((FIXTURES / "deepseek_v2_lite_config.json").read_text())
    dims = lat.Dims.from_config(cfg)
    assert (dims.heads, dims.latent, dims.rope, dims.v_head) == (16, 512, 64, 128)
    plans = [{"kind": "ragged", "row_of": np.array([0, 0, 1]), "slots": np.array([4, 5, 9]),
              "p_end": np.array([0, 0, 0]), "s_start": np.array([4, 4, 0]), "sampled": 2},
             {"kind": "decode", "ctx": np.array([10, 30]), "sampled": 2}]
    ctx = _one_device_ctx(lat, dims, plans)
    peaks = ctx["peaks"]
    step_mfu = spec.load_reader("step_mfu", ROOT).read(ctx)
    flops = lat.step_flops(dims, plans[0]) + lat.step_flops(dims, plans[1])
    assert step_mfu == pytest.approx(100 * flops / (200e-6 * peaks["bf16_flops_per_s"]))
    # by hand: the ragged step attends 1 + 2 + 10 slots in each of 27 layers,
    # each slot 2 x 16 x (576 + 512) FLOPs; row 0 reads slots 4..5, row 1 0..9
    f, b = lat.kernel_work("paged_chunk_attention", dims, ctx["config"], plans[0])
    assert f == 13 * 2 * 16 * (576 + 512) * 27
    assert b == (12 * 576 * 2 + 3 * 16 * (576 + 512) * 2) * 27
    roof = spec.load_reader("paged_chunk_attention_roofline", ROOT).read(ctx)
    assert roof == pytest.approx(100 * work.min_time(f, b, peaks) / 50e-6)
    # the dense formulas would count other work for the same steps
    assert f != ref.chunk_kernel_work(QWEN, 2, 2, plans[0]["row_of"], plans[0]["slots"],
                                      plans[0]["p_end"], plans[0]["s_start"])[0]


ARCH_WORDS = ("gqa", "grouped", "latent", "mla", "expert", "moe", "kv_heads", "head_dim",
              "d_ff", "heads", "lora")


@pytest.mark.parametrize("part", ["harness", "metrics"])
def test_harness_and_readers_name_no_architecture(part):
    for path in sorted((ROOT / "bench" / part).glob("*.py")):
        text = path.read_text().lower()
        found = [w for w in ARCH_WORDS if w in text]
        assert not found, f"{path.name} names {found}"
