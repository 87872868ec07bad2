"""Real-size compiles for a *described* TPU v5e — no chip attached.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached (``on-chip-measurement`` guide §2.3): what
Mosaic/XLA would refuse on the chip — an unaligned lane slice, a kernel
over the 16 MiB scoped fast-memory limit — it refuses here, at no chip
time.  These are compiles only: nothing runs, so they say nothing about
results or speed.

This is the ONE test file that describes a topology.  The call loads
libtpu, which one process at a time may hold, so it lives in a
module-scoped fixture (never at import/collection time) and every compile
happens in the test's own process.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tensorflowonspark_tpu.ops import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A one-chip sharding on the described v5e, with the persistent
    compilation cache off for the module: a compile for a described
    device is written to the cache but cannot be read back without a
    chip, so the next run would warn and recompile anyway."""
    from jax.experimental.compilation_cache.compilation_cache import \
        reset_cache

    from tensorflowonspark_tpu.models import gpt, moe
    from tensorflowonspark_tpu.ops import (grouped_matmul, paged_attention,
                                           power_retention, ssm)

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    # the test process's default backend is the CPU, where the model's
    # paged decode step takes the gather path, its expert layer
    # ``ragged_dot`` and a kernel alone its interpreter: everything this
    # module compiles is for the chip
    seen = [(m, m._on_tpu) for m in (gpt, moe, grouped_matmul,
                                     paged_attention, power_retention, ssm)]
    for m, _ in seen:
        m._on_tpu = lambda: True
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        for m, fn in seen:
            m._on_tpu = fn
        jax.config.update("jax_enable_compilation_cache", prev)
        reset_cache()


def _compile_flash(one_chip, B, T, H, D, *, backward, causal=False,
                   masked=False):
    shape = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16,
                                 sharding=one_chip)
    args = [shape, shape, shape]
    if masked:
        args.append(jax.ShapeDtypeStruct((B, T), jnp.bool_,
                                         sharding=one_chip))

    def fwd(q, k, v, mask=None):
        # interpret=False: the default would pick the interpreter here,
        # because the default backend of the test process is the CPU
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               interpret=False)

    def loss(q, k, v, mask=None):
        return fwd(q, k, v, mask).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    return jax.jit(fn).lower(*args).compile()


#: the widths the repo's own models use (ISSUE 21 item 6)
_SHAPES = {
    "gpt350m_T2048_D64": dict(B=8, T=2048, H=16, D=64, causal=True),
    "gpt2_T1024_D64": dict(B=8, T=1024, H=12, D=64, causal=True),
    "bert_T384_D64_mask": dict(B=24, T=384, H=12, D=64, masked=True),
    "ragged_T100_D64": dict(B=4, T=100, H=12, D=64),
    "llama_T4096_D128": dict(B=1, T=4096, H=32, D=128, causal=True),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("name", sorted(_SHAPES))
def test_flash_attention_compiles_for_v5e(one_chip, name, backward):
    compiled = _compile_flash(one_chip, backward=backward, **_SHAPES[name])
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T,H,backward", [
    (8192, 32, True), (32768, 8, False), (32768, 8, True),
], ids=["bwd_T8192_D128", "fwd_T32768_D128", "bwd_T32768_D128"])
def test_flash_attention_long_context_fits_scoped_vmem(one_chip, T, H,
                                                       backward):
    """Shapes the whole-sequence kernel was refused at (scoped allocation
    32.75M > 16M): K/V and Q/dO now stream by block."""
    compiled = _compile_flash(one_chip, B=1, T=T, H=H, D=128, causal=True,
                              backward=backward)
    assert "tpu_custom_call" in compiled.as_text()


# -- the paged pool's layout (ISSUE 26) -----------------------------------
# The device lays an array out from its shape alone.  A pool whose token
# row is whole 128-lane tiles (``models.gpt.kv_row_width``) comes in
# row-major, the layout the token scatter writes in, so the store runs on
# the donated parameter; a pool of another shape is re-laid before the
# scatter and back after it, once per pool per step.

_POOL_WIDTHS = {"gpt2xl_25x64": dict(num_heads=25, hidden_size=1600),
                "gpt2_12x64": dict(num_heads=12, hidden_size=768)}
_POOL_PROGRAMS = {"decode_B16_T1": (16, 1), "prefill_B1_T256": (1, 256)}


def _compile_paged_step(one_chip, B, T, pool_pages=1024, steps=1, **widths):
    """The paged step through the repo's ``GPT`` (2 layers, the cell's
    1024 x 16-token pool, cache donated), compiled for the described
    chip from shapes alone; ``steps`` > 1 scans it, each step fed the
    last one's tokens, as the batcher's ``decode_block_steps`` does."""
    from tensorflowonspark_tpu.models.gpt import GPT, GPTConfig, init_cache

    cfg = GPTConfig(num_layers=2, intermediate_size=4 * widths["hidden_size"],
                    per_row_positions=True, kv_page_tokens=16,
                    kv_pool_pages=pool_pages, **widths)
    model = GPT(cfg, decode=True)
    params = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.key(0),
                              jnp.zeros((1, 8), jnp.int32))["params"])
    cache = jax.eval_shape(lambda p: init_cache(cfg, p, B), params)

    def one_step(params, cache, tokens):
        logits, vars_ = model.apply({"params": params, "cache": cache},
                                    tokens, mutable=["cache"])
        return jnp.argmax(logits[:, -1], -1), vars_["cache"]

    def step(params, cache, tokens):
        if steps == 1:
            return one_step(params, cache, tokens)

        def body(carry, _):
            nxt, cache = one_step(params, carry[1], carry[0])
            nxt = nxt[:, None].astype(jnp.int32)
            return (nxt, cache), nxt

        (_, cache), seq = jax.lax.scan(body, (tokens, cache), None,
                                       length=steps)
        return seq, cache

    def on_chip(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=one_chip), tree)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip)).compile()
    return cfg, compiled.as_text()


@pytest.mark.parametrize("program", sorted(_POOL_PROGRAMS))
@pytest.mark.parametrize("widths", sorted(_POOL_WIDTHS))
def test_paged_pool_is_stored_in_place_on_v5e(one_chip, widths, program):
    import re

    B, T = _POOL_PROGRAMS[program]
    cfg, text = _compile_paged_step(one_chip, B, T, **_POOL_WIDTHS[widths])
    pool_rows = cfg.kv_pool_pages * cfg.kv_page_tokens
    entry = text[text.index("\nENTRY "):].splitlines()

    pools = [ln for ln in entry if " parameter(" in ln
             and re.search(rf"= bf16\[{pool_rows},\d+\]", ln)]
    assert len(pools) == 2 * cfg.num_layers, pools
    assert all(re.search(rf"= bf16\[{pool_rows},\d+\]\{{1,0[:}}]", ln)
               for ln in pools), "a pool parameter does not enter row-major"

    # a copy of the pool: its leading axes are the pool's tokens, flat or
    # as pages (the embedding table, re-laid every step, is larger still
    # and not this test's)
    tokens = rf"{pool_rows}|{cfg.kv_pool_pages},{cfg.kv_page_tokens}"
    copies = [ln.strip()[:120] for ln in entry
              if re.search(rf"= \w+\[({tokens}),[\d,]+\]\S* copy\(", ln)]
    assert not copies, copies


# -- the decode step attends over the pages in place (ISSUE 29) ------------
# Both serve cells' decode programs at their real widths, rows and pools
# (2 layers): the Mosaic kernel is in the program and fits its fast memory
# (a refusal raises here), and no instruction has the shape of a row's
# whole view any more.  A prefill is the program it was.

_DECODE_CELLS = {
    "gpt2xl_B16": dict(B=16, pool_pages=1024, num_heads=25,
                       hidden_size=1600),
    "lfm2_attention_B32": dict(
        B=32, pool_pages=4096, num_heads=32, num_kv_heads=8,
        hidden_size=2048, max_position_embeddings=2048, pos_encoding="rope",
        rope_base=1e6, norm="rmsnorm", use_bias=False, qk_norm=True)}


@pytest.mark.parametrize("cell", sorted(_DECODE_CELLS))
def test_decode_step_attends_over_the_pages_in_place_on_v5e(one_chip, cell):
    import re

    from tensorflowonspark_tpu.models.gpt import kv_row_width

    widths = dict(_DECODE_CELLS[cell])
    B = widths.pop("B")
    cfg, text = _compile_paged_step(one_chip, B, 1, **widths)
    Hkv = cfg.num_kv_heads or cfg.num_heads
    assert text.count("tpu_custom_call") >= cfg.num_layers
    assert "tfos_paged_decode_attention" in text
    C, pt = cfg.max_position_embeddings, cfg.kv_page_tokens
    W = kv_row_width(Hkv, cfg.head_dim)
    views = (f"{B},{C},{Hkv},{cfg.head_dim}", f"{B},{C // pt},{pt},{W}",
             f"{C},{B},{W}", f"{B},{C},{W}")
    whole = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"= \(?\w+\[(" + "|".join(views) + r")\]", ln)]
    assert not whole, whole


@pytest.mark.parametrize("how,kw,calls", [
    ("block_of_4_steps", {"steps": 4}, 2),
    ("scan_layers", {"scan_layers": True}, 1)])
def test_kernel_compiles_inside_a_scan_on_v5e(one_chip, how, kw, calls):
    """``jit_tfos_decode_block`` scans the step, ``scan_layers`` the
    layers: the kernel is inside the scanned body either way."""
    _, text = _compile_paged_step(one_chip, 16, 1, **kw,
                                  **_POOL_WIDTHS["gpt2xl_25x64"])
    assert text.count("tpu_custom_call") == calls
    assert "16,1024,25,64" not in text


def test_prefill_is_the_gather_path_program_on_v5e(one_chip, monkeypatch):
    """The same text whether the decode step's rule exists or not (both
    compiles from one source line: the text carries the call's frames)."""
    from tensorflowonspark_tpu.models import gpt

    texts = []
    for rule in (None, lambda *a: False):
        if rule is not None:
            monkeypatch.setattr(gpt, "attends_pages_in_place", rule)
        texts.append(_compile_paged_step(
            one_chip, 1, 256, **_POOL_WIDTHS["gpt2xl_25x64"])[1])
    assert "tpu_custom_call" not in texts[0]
    assert texts[0] == texts[1]


# -- the layer pattern at LFM2-8B-A1B's widths (ISSUE 28) -------------------
# One period of the pattern (conv, conv, attention, conv; one dense layer,
# three expert layers) at the published widths, the cell's 32 rows and its
# 4096 x 16-token pool: the grouped matmul, the conv state and the 512-lane
# pool rows as the chip's compiler takes them.  Since ISSUE 34 the grouped
# products of both programs are the kernel ``ops.grouped_matmul`` (two calls
# an expert layer: gate and up with the activation, then down), fed the
# experts' weights in the layout they are stored in.

_LFM2_PROGRAMS = {"decode_B32_T1": (32, 1, False),
                  "prefill_B1_T1024": (1, 1024, True)}


@pytest.mark.parametrize("program", sorted(_LFM2_PROGRAMS))
def test_conv_and_expert_layers_compile_for_v5e(one_chip, program):
    import re

    from tensorflowonspark_tpu.models import moe
    from tensorflowonspark_tpu.models.gpt import GPT, GPTConfig, init_cache

    B, T, padded = _LFM2_PROGRAMS[program]
    cfg = GPTConfig(
        vocab_size=65536, hidden_size=2048, num_layers=4, num_heads=32,
        num_kv_heads=8, intermediate_size=7168,
        max_position_embeddings=2048, pos_encoding="rope", rope_base=1e6,
        norm="rmsnorm", norm_eps=1e-5, mlp="swiglu", use_bias=False,
        qk_norm=True,
        layer_types=("conv", "conv", "full_attention", "conv"),
        num_dense_layers=1, num_experts=32, num_experts_per_tok=4,
        moe_intermediate_size=1792, per_row_positions=True,
        kv_page_tokens=16, kv_pool_pages=4096)
    model = GPT(cfg, decode=True)
    params = jax.eval_shape(
        lambda: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16),
            GPT(cfg).init(jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]))
    cache = jax.eval_shape(lambda p: init_cache(cfg, p, B), params)

    def step(params, cache, tokens, lengths):
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tokens,
            mutable=["cache", moe.STATS],
            **({"lengths": lengths} if padded else {}))
        return jnp.argmax(logits[:, -1], -1), vars_

    def on_chip(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=one_chip), tree)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):].splitlines()
    # two kernel calls per expert layer (``models.moe.streams_experts_once``
    # holds at a decode step's 128 assignments and a prefill's 4096 alike)
    # and no grouped product of XLA's ...
    assert len(re.findall(r"= \S+ custom-call\(.*tfos_grouped_matmul",
                          text)) == 2 * cfg.num_expert_layers == 6
    assert "ragged-dot" not in text
    # ... which read the experts' weights where they lie: no instruction
    # copies or re-lays an operand of a whole layer's experts (231 MB a
    # call, and as much again of temporaries)
    assert not [ln.strip()[:120] for ln in text.splitlines()
                if re.search(r"= bf16\[32,(2048,1792|1792,2048)\]\S* "
                             r"(copy|transpose|fusion)\(", ln)]
    # the attention layer's two pools: 8 x 64 = 512 lanes, row-major
    pools = [ln for ln in entry if " parameter(" in ln
             and re.search(r"= bf16\[65536,512\]", ln)]
    assert len(pools) == 2
    assert all(re.search(r"= bf16\[65536,512\]\{1,0[:}]", ln)
               for ln in pools)
    # the three conv layers' state rows are parameters of the step
    assert sum(1 for ln in entry if " parameter(" in ln
               and re.search(rf"= bf16\[{B},2,2048\]", ln)) == 3
    # the padded prefill computes the head at one position a row
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


# the kernel alone at every shape the lfm2 cell calls it with: a decode
# step's 128 assignments, and prefills of 1, 2 and 4 rows of the 1024
# bucket; its tiles (``ops.grouped_matmul._tiles``) fit the fast memory it
# asks for, and the weights enter as they are stored

_GROUPED_CALLS = {"decode_128": 128, "prefill_1x1024": 4096,
                  "prefill_2x1024": 8192, "prefill_4x1024": 16384}


@pytest.mark.parametrize("call", sorted(_GROUPED_CALLS))
def test_grouped_matmul_compiles_at_the_cells_shapes_on_v5e(one_chip, call):
    from tensorflowonspark_tpu.ops import grouped_matmul as gm

    m, E, H, F = _GROUPED_CALLS[call], 32, 2048, 1792

    def of(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def experts(rows, w_gate, w_up, w_down, counts):
        h = gm.grouped_swiglu(rows, w_gate, w_up, counts,
                              out_dtype=jnp.bfloat16)
        return gm.grouped_dot(h, w_down, counts)

    compiled = jax.jit(experts).lower(
        of(m, H), of(E, H, F), of(E, H, F), of(E, F, H),
        of(E, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert not [ln.strip()[:120] for ln in text.splitlines()
                if " copy(" in ln and "bf16[32," in ln]
    # the walk's metadata and the activations between the two calls
    assert compiled.memory_analysis().temp_size_in_bytes < m * (F * 2 + 64) \
        + (1 << 20)


# -- power-retention layers at Brumby-14B-Base's widths (ISSUE 32) ----------
# Two of the layers at the published widths with the whole vocabulary and
# the untied head, the cell's 16 rows: the decode step's kernel fits its
# fast memory and updates the donated state IN PLACE (one live copy of the
# largest thing on the chip after the weights), and neither program re-lays
# the embedding table or the head.

_BRUMBY_PROGRAMS = {"decode_B16_T1": (16, 1, False),
                    "prefill_B2_T512": (2, 512, True)}


@pytest.mark.parametrize("program", sorted(_BRUMBY_PROGRAMS))
def test_retention_layers_compile_for_v5e(one_chip, program):
    import re

    from tensorflowonspark_tpu.models.gpt import GPT, GPTConfig, init_cache
    from tensorflowonspark_tpu.ops import power_retention

    B, T, padded = _BRUMBY_PROGRAMS[program]
    cfg = GPTConfig(
        vocab_size=151936, hidden_size=5120, num_layers=2, num_heads=40,
        num_kv_heads=8, intermediate_size=17408,
        max_position_embeddings=2048, pos_encoding="rope", rope_base=1e6,
        norm="rmsnorm", mlp="swiglu", use_bias=False, qk_norm=True,
        layer_types=("retention", "retention"), tie_word_embeddings=False,
        per_row_positions=True)
    model = GPT(cfg, decode=True)
    params = jax.eval_shape(
        lambda: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16),
            GPT(cfg).init(jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]))
    cache = jax.eval_shape(lambda p: init_cache(cfg, p, B), params)
    assert {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(
        cache)[0]} == {"index", "ret_state", "ret_norm"}

    def step(params, cache, tokens, lengths):
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tokens, mutable=["cache"],
            **({"lengths": lengths} if padded else {}))
        return jnp.argmax(logits[:, -1], -1), vars_["cache"]

    def on_chip(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=one_chip), tree)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    state = cfg.num_layers * power_retention.state_bytes(B, 8, 128)
    memory = compiled.memory_analysis()
    # the state goes out in the buffers it came in: one live copy
    assert memory.alias_size_in_bytes >= state
    if padded:
        assert "tfos_retention_step" not in text
    else:
        assert "tfos_retention_step" in text
        assert text.count("tpu_custom_call") >= cfg.num_layers
        # beside the weights and the state, a step holds megabytes
        assert memory.temp_size_in_bytes < 0.1 * state
    relaid = [ln.strip()[:120] for ln in text.splitlines()
              if re.search(r"= \w+\[(151936,5120|5120,151936)\]\S* copy\(",
                           ln)]
    assert not relaid, relaid


# -- one mixer a block at Nemotron-3-Nano-30B-A3B's widths (ISSUE 42) -------
# One period of the pattern (Mamba-2, experts, Mamba-2, attention, experts)
# at the published widths with the chip's share (16 of 128 experts, an
# eighth of the vocabulary), the cell's 32 rows and its 2048 x 16-token
# pool: the state-space kernel fits its fast memory and updates the donated
# state IN PLACE, the held experts' two products are the grouped kernel
# (one call with the relu2 epilogue, one down), the shared expert is plain
# products, and attention's 256-lane pool rows are row-major.

_NEMOTRON_PROGRAMS = {"decode_B32_T1": (32, 1, False),
                      "prefill_B4_T256": (4, 256, True)}


def _nemotron_cfg(pattern="MEM*E"):
    from tensorflowonspark_tpu.models.gpt import GPTConfig

    kinds = {"M": "mamba2", "E": "experts", "*": "full_attention"}
    return GPTConfig(
        vocab_size=16384, hidden_size=2688, num_layers=len(pattern),
        num_heads=32, num_kv_heads=2, attn_head_dim=128,
        max_position_embeddings=1024, pos_encoding="none", norm="rmsnorm",
        norm_eps=1e-5, use_bias=False, mixer_only=True,
        layer_types=tuple(kinds[c] for c in pattern), ssm_num_heads=64,
        ssm_head_dim=64, ssm_groups=8, ssm_state_size=128,
        ssm_conv_kernel=4, ssm_chunk=128, tie_word_embeddings=False,
        num_experts=128, num_experts_per_tok=6, moe_intermediate_size=1856,
        experts_held=(0, 16), moe_shared_intermediate_size=3712,
        moe_activation="relu2", routed_scaling_factor=2.5, moe_up_transposed=True,
        per_row_positions=True, kv_page_tokens=16, kv_pool_pages=2048)


def _compile_nemotron(one_chip, cfg, B, T, padded):
    from tensorflowonspark_tpu.models import moe
    from tensorflowonspark_tpu.models.gpt import GPT, init_cache

    model = GPT(cfg, decode=True)
    params = jax.eval_shape(
        lambda: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16),
            GPT(cfg).init(jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]))
    cache = jax.eval_shape(lambda p: init_cache(cfg, p, B), params)

    def step(params, cache, tokens, lengths):
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tokens,
            mutable=["cache", moe.STATS],
            **({"lengths": lengths} if padded else {}))
        return jnp.argmax(logits[:, -1], -1), vars_

    def on_chip(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=one_chip), tree)

    return cache, jax.jit(step, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)).compile()


@pytest.mark.parametrize("program", sorted(_NEMOTRON_PROGRAMS))
def test_mamba2_and_held_expert_layers_compile_for_v5e(one_chip, program):
    import re

    B, T, padded = _NEMOTRON_PROGRAMS[program]
    cfg = _nemotron_cfg()
    cache, compiled = _compile_nemotron(one_chip, cfg, B, T, padded)
    assert {p[-1].key for p, _ in jax.tree_util.tree_flatten_with_path(
        cache)[0]} == {"index", "block_table", "k", "v", "ssm_state",
                       "ssm_conv"}
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):].splitlines()
    # two kernel calls per expert layer, no grouped product of XLA's
    assert len(re.findall(r"= \S+ custom-call\(.*tfos_grouped_matmul",
                          text)) == 2 * cfg.num_expert_layers == 4
    assert "ragged-dot" not in text
    # no instruction copies or re-lays a whole layer's held experts
    assert not [ln.strip()[:120] for ln in text.splitlines()
                if re.search(r"= bf16\[16,(2688,1856|1856,2688)\]\S* "
                             r"(copy|transpose|fusion)\(", ln)]
    # the decode step is the state-space kernel, once a Mamba-2 layer; a
    # block of tokens is the chunked scan and holds none
    calls = sum(1 for ln in text.splitlines()
                if " custom-call(" in ln and "tfos_ssm_step" in ln)
    assert calls == (0 if padded else 2)
    # both states of both Mamba-2 layers are parameters of the step, and
    # the SSM state is updated in place: the donated buffer is the output
    state = rf"f32\[{B},8,128,512\]"
    assert sum(1 for ln in entry if " parameter(" in ln
               and re.search("= " + state, ln)) == 2
    assert sum(1 for ln in entry if " parameter(" in ln
               and re.search(rf"= bf16\[{B},3,6144\]", ln)) == 2
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * B * 8 * 128 * 512 * 4
    # the attention layer's two pools: 2 x 128 = 256 lanes, row-major
    pools = [ln for ln in entry if " parameter(" in ln
             and re.search(r"= bf16\[32768,256\]", ln)]
    assert len(pools) == 2
    assert all(re.search(r"= bf16\[32768,256\]\{1,0[:}]", ln)
               for ln in pools)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
