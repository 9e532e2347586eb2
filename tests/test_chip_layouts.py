"""What only the chip's compiler can say, asked of it without a chip: the
TPU compiler is installed here and compiles for a described v5e. These are
compiles, not runs: no time, no result. One file, the topology described in
a fixture, so that only the worker that runs this file loads libtpu."""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("side_in,temporaries", [(224, False), (256, True)],
                         ids=["same_size", "resized"])
def test_resize_program_crosses_the_host_boundary_in_row_order(one_chip, side_in, temporaries):
    """``device_get`` returns a device layout as strides, and the TPU keeps
    a 4-D image batch with its batch dimension minor-most, so a 4-D result
    reaches the host with each image scattered across the buffer (PERF.md,
    PR 26). The stage program therefore takes and returns (rows, H*W*C):
    both sides row-major, and no temporary where no shape changes."""
    import jax

    from mmlspark_tpu.image import ImageTransformer

    shape = (512, side_in, side_in, 3)
    _, run, _ = ImageTransformer(toFloat=True).resize(224, 224)._pipeline()
    flat = jax.ShapeDtypeStruct((shape[0], int(np.prod(shape[1:]))), np.uint8, sharding=one_chip)
    compiled = run.lower((flat,), shape).compile()
    ((taken,),), _ = compiled.input_formats
    assert taken.layout.major_to_minor == (0, 1)
    assert compiled.output_formats.layout.major_to_minor == (0, 1)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == shape[0] * 224 * 224 * 3 * 4
    assert (memory.temp_size_in_bytes > 0) == temporaries


@pytest.mark.parametrize("slab,side_in,temporaries", [
    (512, 224, 0), (664, 224, 0), (661, 224, 661), (512, 256, 512)],
    ids=["the_cells_twelve", "a_short_last_slab", "slabs_that_end_inside_a_tile", "resized"])
def test_stage_program_over_slabs_holds_no_second_copy_of_the_table(one_chip, slab, side_in, temporaries):
    """A shape group of 6,144 images reaches the stage program as uint8
    slabs (PERF.md, PR 38). Each is staged by itself and written where its
    rows lie in the group's result: no joined uint8 table (0.86 GiB where
    the slabs are concatenated first: the TPU compiler moves the cast behind
    a concatenation wherever it is written), and what comes back is what the
    one-batch program returned, ``(6144, 150528)`` float32 row-major. A slab
    ends on a tile of 8 rows (``_staged`` cuts them so; the group's end may
    not): one that ends inside a tile is cast into a temporary of its own
    size first. Where a stage changes the shape its temporaries are a
    slab's, not the table's."""
    import jax

    from mmlspark_tpu.image import ImageTransformer

    rows = 6144 if side_in == 224 else 2048
    shape = (rows, side_in, side_in, 3)
    _, run, _ = ImageTransformer(toFloat=True).resize(224, 224)._pipeline()
    slabs = tuple(
        jax.ShapeDtypeStruct((min(slab, rows - lo), int(np.prod(shape[1:]))), np.uint8, sharding=one_chip)
        for lo in range(0, rows, slab))
    compiled = run.lower(slabs, shape).compile()
    (taken,), _ = compiled.input_formats
    assert len(taken) == -(-rows // slab) and {t.layout.major_to_minor for t in taken} == {(0, 1)}
    assert compiled.output_formats.layout.major_to_minor == (0, 1)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == rows * 224 * 224 * 3 * 4
    one = temporaries * 224 * 224 * 3 * 4  # a slab's result
    if not temporaries:
        assert memory.temp_size_in_bytes == 0
    elif side_in == 224:  # one slab's cast, never the uint8 table joined (924,844,032 B)
        assert one <= memory.temp_size_in_bytes < 1.25 * one
    else:  # the one-batch program over the same 2,048 images holds 2.7 GiB
        assert one <= memory.temp_size_in_bytes < 1.75 * 2**30


@pytest.mark.parametrize("rows", [512, 300], ids=["full_batch", "short_last_batch"])
def test_device_batch_slices_the_resident_table_into_the_forwards_layout(one_chip, rows):
    """``ImageFeaturizer`` leaves the resized table on the device as the stage
    program returned it, ``(6144, 150528)`` float32 row-major, and
    ``DNNModel``'s batch loop cuts 512 rows out of it there. The slice
    program's result is the 4-D batch in the layout the TPU gives a host
    batch it is handed (batch minor-most), its temporaries are two copies of
    one batch, and the table itself is not copied (PERF.md, PR 32)."""
    import jax

    from mmlspark_tpu.dnn.model import _build_device_batch

    table = jax.ShapeDtypeStruct((6144, 224 * 224 * 3), np.float32, sharding=one_chip)
    lo = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    compiled = _build_device_batch().lower(
        table, lo, rows, (512, 224, 224, 3), np.dtype("float32")).compile()
    (taken, _), _ = compiled.input_formats
    assert taken.layout.major_to_minor == (0, 1)
    assert compiled.output_formats.layout.major_to_minor == (1, 3, 2, 0)
    memory = compiled.memory_analysis()
    batch = 512 * 224 * 224 * 3 * 4
    assert memory.output_size_in_bytes == batch
    assert memory.temp_size_in_bytes < 3 * batch


def _compile_off(fn, *shapes):
    """Compile with the persistent cache off: an entry written for a
    described device cannot be read back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_blocked_attention_at_the_published_widths_never_holds_whole_scores(one_chip, window):
    """One dispatch of the language-model cell: 4 rows of 8,192 tokens, 32
    query heads over 4 key/value heads of 128. Whole float32 scores would
    be 4 x 32 x 8,192^2 x 4 B = 32 GiB; the kernel (it compiles as written:
    a ``tpu_custom_call``) needs only the transposed copies of its operands
    (PERF.md, PR 27)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.attention import blocked_attention

    q = jax.ShapeDtypeStruct((4, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 8192, 4, 128), jnp.bfloat16, sharding=one_chip)
    compiled = _compile_off(lambda q, k, v: blocked_attention(q, k, v, window=window), q, kv, kv)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 4 * 8192 * 32 * 128 * 2
    assert memory.temp_size_in_bytes < 2**30 and "tpu_custom_call" in compiled.as_text()


def test_blocked_attention_at_the_latent_widths_compiles_with_a_group_of_one(one_chip):
    """One dispatch of the latent-attention cell: 1 row of 16,384 tokens, 32
    heads each with its own key of 192 (128 + the 64 rotary columns every
    head shares) and value of 128. The 192-lane key tile compiles as written
    (a ``tpu_custom_call``) and the kernel needs only the transposed copies
    of its operands (PERF.md, PR 31)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.attention import blocked_attention

    qk = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one_chip)
    compiled = _compile_off(lambda q, k, v: blocked_attention(q, k, v), qk, qk, v)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 16384 * 32 * 128 * 2
    assert memory.temp_size_in_bytes < 2**30 and "tpu_custom_call" in compiled.as_text()


def _expert_products_read_in_place(text, E, D, F, rows):
    """The three grouped expert products are Pallas grouped matmuls named
    ``gmm``, one HLO name each, and XLA's grouped product is gone; no
    operation makes a layer's ``(E, D, F)`` or ``(E, F, D)`` matrix (a slice
    of the stacks, a copy, a transposition): the stacks are read where they
    lie, every layer's groups one after another."""
    import re

    products = set(re.findall(r"%(gmm[\w.\-]*) = f32\[" + f"{rows}," + f"(?:{D}|{F})" + r"\]", text))
    a_layers_matrix = re.findall(r"%([\w.\-]+) = bf16\[(?:1,)?" + f"{E},(?:{D},{F}|{F},{D})" + r"\]\S* "
                                 r"(?!bitcast|parameter|get-tuple-element)", text)
    assert len(products) == 3 and "ragged-dot" not in text, products
    assert not a_layers_matrix, a_layers_matrix


def test_the_latent_decoder_at_the_published_widths_fits_beside_its_weights(one_chip):
    """The whole program of ``joyai-llm-flash.score-16k``, one dispatch of
    16,384 tokens: 10.35 GiB of weights leave 5.3 GiB of a v5e's 15.75 for
    temporaries, and ``memory_stats`` on the chip does not count them, so
    the compiler is the one that can say (2.47 GiB with a layer's matrices
    copied out of the scan, 1.72 with the stacks read in place: PERF.md §4,
    §6). The attention kernel is one HLO name a stack, which the roofline
    metric reads by; the three grouped expert products read all 4 x 256
    groups of the expert stacks where they lie: no layer's 2.4 GB of
    matrices is copied out of them."""
    import json
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.mla_moe import init_mla_moe, mla_moe_apply

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "joyai-llm-flash.json")) as f:
        config = json.load(f)["params"]
    tree = jax.eval_shape(lambda k: init_mla_moe(k, config), jax.random.PRNGKey(0))
    tree = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    compiled = _compile_off(lambda p, x: mla_moe_apply(p, x, config), tree, tokens)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 11_116_285_952
    assert memory.temp_size_in_bytes < 3.5 * 2**30
    text = compiled.as_text()
    assert len(set(re.findall(r"%(attn_full[\w.\-]*) =", text))) == 2  # the dense stack's and the expert stack's
    _expert_products_read_in_place(text, 256, 2048, 768, 16384 * 8)


def test_topk_experts_at_the_published_widths_use_the_grouped_product(one_chip):
    """32,768 tokens, 128 experts of width 1,024, top-8: XLA:TPU lowers
    ``lax.ragged_dot`` to its own grouped kernel (a ``tpu_custom_call``),
    not to 128 masked dense products."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.expert_parallel import moe_topk

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    experts = {"gate": spec((128, 2048, 1024)), "up": spec((128, 2048, 1024)),
               "down": spec((128, 1024, 2048))}
    scores = spec((32768, 128), jnp.float32)
    compiled = _compile_off(lambda x, s, e: moe_topk(x, s, s, e, 8, 2.826),
                            spec((32768, 2048)), scores, experts)
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 3 and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2**30


@pytest.mark.parametrize("shape", ["short_convolution", "hybrid_in_place"])
def test_topk_experts_return_their_rows_through_the_combine_kernel_and_no_copy(one_chip, shape):
    """``moe_topk`` at the short-convolution cell's shape (32,768 tokens, 32
    experts of 1,792, top-4, ``lax.ragged_dot``) and the hybrid cell's
    (16,384 tokens, 512 groups of 1,856 over 2,688 read in place, top-6, the
    Pallas grouped matmul): the only float32 ``(T*k, D)`` instruction is the
    down product's own result, nothing is laid out ``(T, k, D)`` (the
    parent's was padded to 8 of 4 or 6), and the rows go back through one
    ``moe_combine`` kernel. The combine alone held 2,048 and 2,352 MiB of
    temporaries as the parent wrote it and holds none of that size now
    (PERF.md); the whole of ``moe_topk`` holds 1,370 MiB at the
    hybrid's shape where it held 2,361, and at the short-convolution cell's
    no more than before, where the gate and up products' 2.25 GiB are the
    peak."""
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.moe_decoder import relu2
    from mmlspark_tpu.ops.expert_parallel import _combine, moe_topk

    def spec(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    if shape == "short_convolution":
        T, E, F, D, k = 32768, 32, 1792, 2048, 4
        experts = {n: spec(s) for n, s in (("gate", (E, D, F)), ("up", (E, D, F)), ("down", (E, F, D)))}
        compiled = _compile_off(lambda x, s, e: moe_topk(x, s, s, e, k), spec((T, D)), spec((T, E), jnp.float32),
                                experts)
        products, most = "ragged-dot", 2306 * 2**20
    else:
        T, E, F, D, k = 16384, 128, 1856, 2688, 6
        experts = {"up": spec((4 * E, D, F)), "down": spec((4 * E, F, D))}
        compiled = _compile_off(lambda x, s, e, g: moe_topk(x, s, s, e, k, 2.5, relu2, g), spec((T, D)),
                                spec((T, E), jnp.float32), experts, spec((), jnp.int32))
        products, most = "gmm", 1400 * 2**20
    text = compiled.as_text()
    rows = re.findall(r"%([\w.\-]+) = f32\[" + f"{T * k},{D}" + r"\]", text)
    assert rows and all(name.startswith(products) for name in rows), rows
    assert f"f32[{T},{k},{D}]" not in text
    assert len(set(re.findall(r"%(moe_combine[\w.\-]*) = f32\[" + f"{T},{D}" + r"\]", text))) == 1
    assert compiled.memory_analysis().temp_size_in_bytes <= most

    def parent(ys, back, w):
        return (ys[back].reshape(T, k, D) * w[:, :, None]).sum(axis=1)

    ys, back, weights = spec((T * k, D), jnp.float32), spec((T * k,), jnp.int32), spec((T, k), jnp.float32)
    was = _compile_off(parent, ys, back, weights).memory_analysis().temp_size_in_bytes
    now = _compile_off(lambda *a: _combine(*a, False), ys, back, back, spec((E,), jnp.int32),
                       weights).memory_analysis().temp_size_in_bytes
    assert was - now >= T * k * D * 4


def test_the_state_space_scan_at_the_published_widths_compiles_as_one_kernel(one_chip):
    """One dispatch of the hybrid cell's mixer: 1 row of 16,384 tokens, 64
    heads of 64 in 8 groups, state 128, chunks of 128. The kernel compiles as
    written (a ``tpu_custom_call`` named ``ssd_scan``: two heads side by side
    in 128 lanes, the closing state's product contracting the chunk's
    positions on both sides), and beside its operands it needs only the two
    small float32 layouts of the cumulative decays (PERF.md, PR 33)."""
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.ssd import ssd_scan

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S = 16384
    compiled = _compile_off(
        ssd_scan, spec((1, S, 64, 64)), spec((1, S, 64), jnp.float32), spec((64,), jnp.float32),
        spec((1, S, 8, 128)), spec((1, S, 8, 128)), spec((64,), jnp.float32))
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == S * 64 * 64 * 2
    assert memory.temp_size_in_bytes < 2**28
    assert len(set(re.findall(r"%(ssd_scan[\w.\-]*) =", compiled.as_text()))) == 1


def test_blocked_attention_at_a_group_of_sixteen_holds_in_vmem_as_written(one_chip):
    """The hybrid cell's one attention block: 1 row of 16,384 tokens, 32 query
    heads over 2 key/value heads of 128. A grid step's score product has
    16 x 128 = 2,048 rows, four times Trinity's; its float32 scores, masks
    and accumulator hold under the kernel's 100 MiB VMEM limit with the
    query block of 128 unchanged (PERF.md, PR 33)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.attention import blocked_attention

    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16, sharding=one_chip)
    compiled = _compile_off(lambda q, k, v: blocked_attention(q, k, v), q, kv, kv)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 16384 * 32 * 128 * 2
    assert memory.temp_size_in_bytes < 2**30 and "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("product_dtype", ["bfloat16", "float8_e4m3fn"], ids=["as_stated", "float8_control"])
def test_the_hybrid_decoder_at_the_published_widths_fits_beside_its_weights(one_chip, product_dtype):
    """The whole program of ``nemotron-3-nano.score-16k``, one dispatch of
    16,384 tokens: 11.31 GiB of weights, at the published widths, leave
    4.4 GiB of a v5e's 15.75 for temporaries, and ``memory_stats`` on the
    chip does not count them, so the compiler is the one that can say
    (2.74 GiB, 14.05 in all: PERF.md, PR 34; 3.35 and 14.67 at PR 33). One
    ``lax.scan`` over the four units makes each kernel one HLO name: the scan
    kernel, the attention kernel under its conditional, and the two grouped
    expert products, Pallas grouped matmuls named ``gmm`` that read all
    4 x 128 groups of a stack where they lie. **No operation makes a unit's
    1.2 GiB matrix**: no slice of the stacks, no copy, and the up stack, which
    the chip keeps with its 2,688 minor (1,856 is off the 128 lanes), is
    handed over as stored and not transposed; XLA's own grouped product,
    which tiled 1,856 by 128 and ran at a tenth of the peak, is gone.

    ``float8_control``: the benchmark's lower-precision control rounds a copy
    of a unit's matrices whatever is done, so there the stacks go through the
    scan to XLA's grouped product, PR 33's program, which fits as it did."""
    import json
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.nemotron_h import init_nemotron_h, nemotron_h_apply

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "nemotron-3-nano.json")) as f:
        config = dict(json.load(f)["params"], product_dtype=product_dtype)
    tree = jax.eval_shape(lambda k: init_nemotron_h(k, config), jax.random.PRNGKey(0))
    assert tree["experts"]["e_up"].shape == (4, 128, 2688, 1856) and tree["experts"]["e_down"].shape == (4, 128, 1856, 2688)
    tree = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    compiled = _compile_off(lambda p, x: nemotron_h_apply(p, x, config), tree, tokens)
    memory = compiled.memory_analysis()
    assert 12_145_796_608 <= memory.argument_size_in_bytes < 12_145_796_608 + 2**20
    text = compiled.as_text()
    assert len(set(re.findall(r"%(ssd_scan[\w.\-]*) =", text))) == 1
    assert len(set(re.findall(r"%(attn_full[\w.\-]*) =", text))) == 1
    products = {kind: set(re.findall(r"%(" + kind + r"[\w.\-]*) = f32\[98304,(?:1856|2688)\]", text))
                for kind in ("gmm", "ragged-dot-none")}
    a_units_matrix = re.findall(r"%([\w.\-]+) = bf16\[(?:1,)?(?:128|512),(?:1856,2688|2688,1856)\]\S* (?!bitcast|parameter|get-tuple-element)", text)
    if product_dtype == "bfloat16":
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes <= 14.2 * 2**30
        assert len(products["gmm"]) == 2 and not products["ragged-dot-none"] and "ragged-dot" not in text
        assert not a_units_matrix, a_units_matrix
    else:
        assert memory.temp_size_in_bytes < 3.5 * 2**30
        assert len(products["ragged-dot-none"]) == 2 and not products["gmm"]
        assert a_units_matrix  # the copies, rounded


def test_blocked_attention_at_a_key_head_of_64_lowers_as_written(one_chip):
    """One dispatch of the short-convolution cell's attention layer: 4 rows of
    8,192 tokens, 32 query heads over 8 key/value heads of **64**. A key/value
    block is half a lane tile wide, the score product contracts over 64 and
    the value product writes 64 lanes; Mosaic lowers ``q_ref[...].reshape(G *
    bq, 64)`` and the ``(padded, 64)`` whole-sequence blocks as written (a
    ``tpu_custom_call``), with only the transposed copies of its operands
    beside it (PERF.md, PR 37)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.attention import blocked_attention

    q = jax.ShapeDtypeStruct((4, 8192, 32, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 8192, 8, 64), jnp.bfloat16, sharding=one_chip)
    compiled = _compile_off(lambda q, k, v: blocked_attention(q, k, v), q, kv, kv)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 4 * 8192 * 32 * 64 * 2
    assert memory.temp_size_in_bytes < 2**30 and "tpu_custom_call" in compiled.as_text()


def test_the_short_convolution_decoder_at_the_published_widths_fits_beside_its_weights(one_chip):
    """The whole program of ``lfm2-8b-a1b.score-8k``, one dispatch of 4 x
    8,192 tokens over 16 layers: 10.06 GiB of weights (the tied head held
    once) leave 5.7 GiB of a v5e's 15.75 for temporaries, which
    ``memory_stats`` on the chip does not count, so the compiler is the one
    that can say (3.17 GiB, 13.23 in all, with a layer's matrices copied out
    of the scan; 2.93 and 12.98 with the stacks read in place: PERF.md §4,
    §6). The attention kernel is one HLO name (the dense layers hold none,
    so only the expert scan calls it, under its conditional), which the
    roofline metric reads by; the three grouped expert products read all
    14 x 32 groups of the expert stacks where they lie."""
    import json
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.lfm2_moe import init_lfm2_moe, lfm2_moe_apply

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)["params"]
    tree = jax.eval_shape(lambda k: init_lfm2_moe(k, config), jax.random.PRNGKey(0))
    assert "head" not in tree and tree["moe"]["e_up"].shape == (14, 32, 2048, 1792)
    assert tree["conv"]["in_proj"].shape == (12, 2048, 6144) and tree["attention"]["wk"].shape == (4, 2048, 512)
    tree = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    tokens = jax.ShapeDtypeStruct((4, 8192), jnp.int32, sharding=one_chip)
    compiled = _compile_off(lambda p, x: lfm2_moe_apply(p, x, config), tree, tokens)
    memory = compiled.memory_analysis()
    assert 10_798_258_944 <= memory.argument_size_in_bytes < 10_798_258_944 + 2**20
    assert memory.temp_size_in_bytes < 3.6 * 2**30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + memory.output_size_in_bytes < 13.7 * 2**30
    text = compiled.as_text()
    assert len(set(re.findall(r"%(attn_full[\w.\-]*) =", text))) == 1
    _expert_products_read_in_place(text, 32, 2048, 1792, 32768 * 4)


def test_the_window_and_full_attention_decoder_at_the_published_widths_fits_beside_its_weights(one_chip):
    """The whole program of ``trinity-mini.score-8k``, one dispatch of 4 x
    8,192 tokens over 6 layers: 8.02 GiB of weights, and 3.76 GiB of
    temporaries with a layer's matrices sliced out of the scan, which the
    compiler no longer needs (3.60: PERF.md §6). The three grouped
    expert products read all 4 x 128 groups of the expert stacks where they
    lie."""
    import json
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.afmoe import afmoe_apply, init_afmoe

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "trinity-mini.json")) as f:
        config = json.load(f)["params"]
    tree = jax.eval_shape(lambda k: init_afmoe(k, config), jax.random.PRNGKey(0))
    assert tree["moe"]["e_up"].shape == (4, 128, 2048, 1024)
    tree = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    tokens = jax.ShapeDtypeStruct((4, 8192), jnp.int32, sharding=one_chip)
    compiled = _compile_off(lambda p, x: afmoe_apply(p, x, config), tree, tokens)
    memory = compiled.memory_analysis()
    assert 8_613_110_784 <= memory.argument_size_in_bytes < 8_613_110_784 + 2**20
    assert memory.temp_size_in_bytes <= 3.76 * 2**30
    text = compiled.as_text()
    assert len(set(re.findall(r"%(attn_window[\w.\-]*) =", text))) == 2  # the dense stack's and the expert stack's
    _expert_products_read_in_place(text, 128, 2048, 1024, 32768 * 8)


def test_ssd_scan_at_a_state_of_256_and_heads_of_128_lowers(one_chip):
    """The scan at the dense hybrid cell's shapes: 1 row of 16,384 tokens, 32
    heads of **128** (one head to the 128 lanes, so none are met side by side)
    in 2 groups of 16, a state of **256**: the VMEM scratch is (16, 256, 128)
    float32, 2 MiB. As first written a lone head's ``(1, 1)`` decay was
    broadcast in sublanes and lanes at once, which Mosaic refuses; it is
    selected onto its lanes first (PERF.md, PR 39)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.ssd import ssd_scan

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bc = shape((1, 16384, 2, 256), jnp.bfloat16)
    heads = shape((32,), jnp.float32)
    compiled = _compile_off(lambda x, dt, A, B, C, D: ssd_scan(x, dt, A, B, C, D),
                            shape((1, 16384, 32, 128), jnp.bfloat16), shape((1, 16384, 32), jnp.float32),
                            heads, bc, bc, heads)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 16384 * 32 * 128 * 2
    assert memory.temp_size_in_bytes < 2**28 and "tpu_custom_call" in compiled.as_text()


def test_blocked_attention_at_a_group_of_five_lowers_as_written(one_chip):
    """One dispatch of the dense hybrid cell's attention: 1 row of 16,384
    tokens, 20 query heads over 4 key/value heads of 128, so a grid step's
    score product has 5 x 128 = 640 rows (PERF.md, PR 39)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.attention import blocked_attention

    q = jax.ShapeDtypeStruct((1, 16384, 20, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one_chip)
    compiled = _compile_off(lambda q, k, v: blocked_attention(q, k, v), q, kv, kv)
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 16384 * 20 * 128 * 2
    assert memory.temp_size_in_bytes < 2**30 and "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("product_dtype", ["bfloat16", "float8_e4m3fn"], ids=["as_stated", "the_float8_control"])
def test_the_dense_hybrid_decoder_at_the_published_widths_fits_beside_its_weights(one_chip, product_dtype):
    """The whole program of ``falcon-h1-34b.score-16k``, one dispatch of 1 x
    16,384 tokens over 6 layers: 9.79 GiB of weights leave 5.96 GiB of a
    v5e's 15.75 for temporaries, which ``memory_stats`` on the chip does not
    count (2.44 GiB as stated, 12.23 in all: PERF.md, PR 39). One stack
    under one scan: the attention kernel and the scan kernel are one HLO
    name each, which the roofline metric reads by."""
    import json
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.falcon_h1 import falcon_h1_apply, init_falcon_h1

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "falcon-h1-34b.json")) as f:
        config = dict(json.load(f)["params"], product_dtype=product_dtype)
    tree = jax.eval_shape(lambda k: init_falcon_h1(k, config), jax.random.PRNGKey(0))
    assert tree["layers"]["in_proj"].shape == (6, 5120, 9248) and tree["head"].shape == (5120, 261120)
    tree = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    compiled = _compile_off(lambda p, x: falcon_h1_apply(p, x, config), tree, tokens)
    memory = compiled.memory_analysis()
    assert 10_509_189_376 <= memory.argument_size_in_bytes < 10_509_189_376 + 2**20
    assert memory.temp_size_in_bytes < 2.8 * 2**30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + memory.output_size_in_bytes < 12.7 * 2**30
    text = compiled.as_text()
    assert len(set(re.findall(r"%(attn_full[\w.\-]*) =", text))) == 1
    assert len(set(re.findall(r"%(ssd_scan[\w.\-]*) =", text))) == 1


def test_the_leafwise_tree_streams_u_at_the_head_of_a_round_and_leaves_after_the_routing(one_chip):
    """The fit cell's tree program (1,000,000 x 28 rows of 256 bins, 31 leaves,
    the resident int8 one-hot ``U`` of 7,168 x 1,000,448): the program holds two
    ``U`` x panel contractions, the root's before the loop and one in the loop's
    body, scheduled ahead of everything the body's routing does; the condition
    reads scalars and there is no conditional. So the round that spends the
    leaf budget routes its rows, the loop leaves, and no pass is built for
    children nothing would read: a 31-leaf tree streams ``U`` six times, not
    seven. ``U`` is an argument read in place: the program's temporaries are
    megabytes (PERF.md, PR 36)."""
    import re
    from functools import partial

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.lightgbm.train import TrainOptions, _build_tree_leafwise, _hist_fn
    from mmlspark_tpu.ops.u_histogram import make_u_spec, u_bytes

    rows, features, bins = 1_000_000, 28, 256
    opts = TrainOptions(objective="binary", num_leaves=31, max_bin=bins - 1)
    spec = make_u_spec(bins, features, [bins] * features)
    assert u_bytes(rows, spec) == 7168 * 1_000_448

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    per_row = shape((rows,), jnp.float32)
    build = partial(_build_tree_leafwise, num_bins=bins, opts=opts, histf=_hist_fn(opts, None, spec), u_spec=spec)
    compiled = _compile_off(
        lambda b, g, h, c, e, m, u: build(b, g, h, c, e, m, u=u),
        shape((rows, features), jnp.uint8), per_row, per_row, per_row,
        shape((features, bins - 1), jnp.float32), shape((features,), jnp.float32),
        shape((7168, 1_000_448), jnp.int8))
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 7168 * 1_000_448 and memory.temp_size_in_bytes < 64 * 2**20
    lines = compiled.as_text().split("\n")
    assert not any(" conditional(" in line for line in lines)
    passes = [i for i, line in enumerate(lines)
              if re.search(r"= f32\[7168,\d+\]\S* fusion\(.*hist_pass/dot_general", line)]
    in_body, root = passes  # the entry computation is printed last
    assert "f32[7168,3]" in lines[root] and "/while/" not in lines[root]
    assert "f32[7168,24]" in lines[in_body] and "/while/body/hist_pass/" in lines[in_body]
    # the module is scheduled, so a computation's lines are in the order they run
    opens = max(i for i in range(in_body) if lines[i].endswith("{") and not lines[i].startswith(" "))
    closes = min(i for i in range(in_body, len(lines)) if lines[i].startswith("}"))
    routing = [i for i in range(opens, closes) if "/while/body/route/" in lines[i]]
    assert routing and in_body < routing[0]
    assert not any("hist_pass" in line for line in lines if "/while/cond/" in line)


def test_the_sparse_attention_kernels_at_the_cells_shapes_lower_as_written(one_chip):
    """One dispatch of ``keye-vl2-30b-a3b.score-32k``: 1 x 32,768 tokens, an
    indexer of 16 heads of 64 keeping 2,048 keys a query, 32 query heads over
    4 of 128. ``dsa_index`` holds a block's scores over every key in VMEM as
    order keys (128 x 32,768 x 4 B = 16 MiB) beside its blocks (the indexer
    key sequence 4 MiB, queries and bitmask 0.5 MiB each); a count of the
    search is a (128, 128) float32 carry held in registers through its sweep.
    The compile, which enforces the kernel's limit of 100 MiB, fits it in
    18.5 MiB and refuses 18. It writes only the bitmask (32,768 x 1,024
    int32, 128 MiB): no (T, T) scores in HBM, no sort. ``dsa_attn`` reads the
    bitmask and the key blocks its scalar table names."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.sparse_attention import dsa_attn, dsa_index, words_for

    S = 32768
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    index = _compile_off(lambda qi, w, ki: dsa_index(qi, w, ki, 2048), shape((1, S, 16, 64), jnp.bfloat16),
                         shape((1, S, 16), jnp.float32), shape((1, S, 64), jnp.bfloat16))
    memory = index.memory_analysis()
    assert words_for(S) == 1024 and memory.output_size_in_bytes == S * 1024 * 4
    assert memory.temp_size_in_bytes < 2**27 and "sort" not in index.as_text()
    assert "tpu_custom_call" in index.as_text()
    attn = _compile_off(dsa_attn, shape((1, S, 32, 128), jnp.bfloat16), shape((1, S, 4, 128), jnp.bfloat16),
                        shape((1, S, 4, 128), jnp.bfloat16), shape((1, S, 1024), jnp.int32))
    memory = attn.memory_analysis()
    assert memory.output_size_in_bytes == S * 32 * 128 * 2 and memory.temp_size_in_bytes < 2**29
    assert "tpu_custom_call" in attn.as_text()


@pytest.mark.parametrize("product_dtype", ["bfloat16", "float8_e4m3fn"], ids=["as_stated", "the_float8_control"])
def test_the_sparse_attention_decoder_at_the_published_widths_fits_beside_its_weights(one_chip, product_dtype):
    """The whole program of ``keye-vl2-30b-a3b.score-32k``, one dispatch of 1
    x 32,768 tokens over 6 layers: 8.15 GiB of weights and 3.13 GiB of
    temporaries (11.28 in all, of a v5e's 15.75). One stack
    under one scan: each kernel is one HLO name, which the roofline metrics
    read by; as stated the three grouped expert products read all 6 x 128
    groups where they lie."""
    import json
    import re

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.keye_vl2 import init_keye_vl2, keye_vl2_apply

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "keye-vl2-30b-a3b.json")) as f:
        config = dict(json.load(f)["params"], product_dtype=product_dtype)
    tree = jax.eval_shape(lambda k: init_keye_vl2(k, config), jax.random.PRNGKey(0))
    assert tree["layers"]["e_up"].shape == (6, 128, 2048, 768) and tree["head"].shape == (2048, 151936)
    tree = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    tokens = jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one_chip)
    compiled = _compile_off(lambda p, x: keye_vl2_apply(p, x, config), tree, tokens)
    memory = compiled.memory_analysis()
    assert 8_749_244_928 <= memory.argument_size_in_bytes < 8_749_244_928 + 2**20
    assert memory.temp_size_in_bytes < 3.4 * 2**30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + memory.output_size_in_bytes < 11.6 * 2**30
    text = compiled.as_text()
    for kernel in ("dsa_index", "dsa_attn", "moe_combine"):
        assert len(set(re.findall(r"%(" + kernel + r"[\w.\-]*) =", text))) == 1, kernel
    if product_dtype == "bfloat16":
        _expert_products_read_in_place(text, 128, 2048, 768, 32768 * 8)
