"""The train cells' steps, compiled for a described TPU v5e over the four
chips or one (``tests/chip_compile.py`` has the how and the why)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import kinds
from chip_compile import KERNEL, _on, as_tpu, one_chip, topo  # noqa: F401
from ray_tpu.models import config as mcfg
from ray_tpu.models import transformer
from ray_tpu.ops.moe import KERNEL_MOE_ROWS_BLANK

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_GATHER = re.compile(
    r"= (\S+) all-gather(?:-start)?\(.*?channel_id=(\d+)")


def _computations(text):
    """The compiled module's computations: name -> instruction lines."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip())
    return comps


def _gathers(lines):
    """(dimensions of the result, channel) of each all-gather in ``lines``;
    an asynchronous one's fusions repeat the instruction under one channel."""
    return [(tuple(int(d) for d in re.findall(
        r"\[([\d,]*)\]", m.group(1))[-1].split(",") if d), m.group(2))
        for m in map(_GATHER.search, lines) if m]


def _loop_gathers(text):
    """The all-gathers that run once a trip: those of every computation
    that is a ``while`` body, and of what it calls."""
    comps = _computations(text)
    seen, todo = set(), re.findall(r"\bwhile\([^\n]*?body=%?([\w.\-]+)", text)
    assert todo, "no loop in the step"
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += [c for ln in comps[name] for c in _CALLED.findall(ln)]
    return _gathers([ln for name in seen for ln in comps[name]])


def _assert_head_gathered_once_a_pass(text, cfg, batch, chunk=512):
    """PR 41: the chunked loss's two loops read the head whole over the
    batch axes.  Closed over sharded it was gathered in both bodies, once a
    chunk, and the backward's body gathered the chunk's ``dlog`` over the
    batch besides: sixteen synchronous passes of the head a step."""
    h, v = cfg.hidden_size, cfg.vocab_size
    head = ((h, v), (v, h))
    once_a_trip = [dims for dims, _ in _loop_gathers(text)
                   if dims in head + ((batch, chunk, v),)]
    assert not once_a_trip, once_a_trip
    whole = {ch for dims, ch in _gathers(text.splitlines()) if dims in head}
    assert len(whole) <= 2, whole


def _compiled_train_step(devices, cfg, init_params, train):
    """``make_train_step`` over ``devices`` of the described chips, compiled
    at ``train``'s sizes (the keys of a configuration file's ``train``)."""
    from ray_tpu.parallel import MeshSpec, make_optimizer, make_train_step
    from ray_tpu.parallel.train_step import TrainState, state_shardings

    mesh = MeshSpec(**train["mesh"]).build(devices)
    assert isinstance(mesh, Mesh) and mesh.size == len(devices)
    opt = make_optimizer(**train["optimizer"])

    def init(key):
        params = init_params(key, cfg, jnp.float32)
        return TrainState(params=params, opt_state=opt.init(params),
                          step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    sh = state_shardings(cfg, mesh, opt, shapes)
    step = make_train_step(cfg, mesh, opt, sh, remat=train["remat"])
    tok = jax.ShapeDtypeStruct(
        (train["global_batch"], train["sequence_length"]), jnp.int32,
        sharding=step.batch_sharding)
    compiled = step._jitted.lower(
        _on(sh, shapes), {"tokens": tok, "targets": tok}).compile()
    return compiled, shapes, sh


@pytest.mark.parametrize("impl", ["auto", "splash"])
def test_sharded_train_step_compiles(topo, as_tpu, impl):
    """llama-1b widths, depth cut to 2 layers, ``MeshSpec(fsdp=-1)`` over the
    four chips.  ``attention_impl="auto"``, the default, is the README quick
    start: the flash kernel must sit in a shard_map, or the compiler refuses
    the step with "Mosaic kernels cannot be automatically partitioned".
    ``"splash"`` takes the same wrap."""
    import dataclasses

    assert mcfg.llama_1b().attention_impl == "auto"
    cfg = dataclasses.replace(mcfg.llama_1b(), num_layers=2,
                              attention_impl=impl)
    compiled, shapes, sh = _compiled_train_step(
        topo.devices, cfg, transformer.init_params,
        dict(mesh={"fsdp": -1}, optimizer={}, remat="save_acts",
             global_batch=8, sequence_length=2048))
    text = compiled.as_text()
    assert text.count(KERNEL) >= 2, "no attention kernel in the sharded step"
    for collective in ("all-gather", "reduce-scatter"):
        assert collective in text, f"fsdp step without {collective}"
    # fsdp shards the big weights: each device holds about a quarter
    wq = sh.params["blocks"]["attn"]["wq"]
    assert isinstance(wq, NamedSharding) and wq.spec != P()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(shapes))
    assert per_device < 0.3 * total + 1e6
    _assert_head_gathered_once_a_pass(text, cfg, batch=8)


def test_fsdp_cell_step_gathers_the_head_once_a_pass(topo, as_tpu):
    """``train-fsdp4-s4096``'s own step: its configuration file, 8 layers,
    4 x 4,096 tokens over the four chips."""
    cell = "mistral-7b-v0.3-train-l8"
    doc, cfg = kinds.cell_doc("mistral", cell), kinds.cell_cfg("mistral", cell)
    compiled, _, _ = _compiled_train_step(
        topo.devices, cfg, kinds.load("mistral").init_params, doc["train"])
    _assert_head_gathered_once_a_pass(
        compiled.as_text(), cfg, batch=doc["train"]["global_batch"])
    # 13.21e9 at the program's peak, arguments included (12.81e9 at the
    # parent; sandbox compile, PR 41): each chip's own float32 sum of the
    # head's gradient, 0.40e9 more than a quarter of it.  Arguments +
    # temporaries, the sum ``benchmark/runners/train.py`` reports, read
    # 16.29e9 (14.78e9): 0.80e9 for that 0.40e9, and no measure of what a
    # chip of 16.91e9 holds (the share cell's step below reads 19.9e9 so,
    # and runs).  With the backward one kernel (PR 48) the compiler orders
    # the layer's backward otherwise and keeps other buffers in its second
    # memory space: 13.35e9 at the peak and 16.57e9 by the sum, whatever
    # VMEM the kernel asks for (sandbox compiles, PR 48).
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes < 13.5e9, mem.peak_memory_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.7e9


# ------------- latent attention + dropless experts trained on one chip (PR 39)

def test_share_train_step_fits_one_chip_and_runs_the_counted_kernels(
        topo, as_tpu):
    """``kimi-vl-a3b-train-l6-e8`` as its cell runs it: 2 x 8,192 tokens,
    float32 AdamW state of 668.9M parameters (8.03 GB in place), one chip.
    Reading 15.26e9 bytes at the program's peak, arguments included
    (``peak_memory_in_bytes``; sandbox compile, PR 45 and again PR 48;
    15.86e9 at PR 39, with the attention heads padded to 256 lanes), 15.53e9
    since PR 59 (the walks over the sorted layout's live rows: the most the
    step's buffers need at once FELL, 14.69e9 to 13.80e9 by the compiler's
    own live ranges, and the heap it packs them into grew, 7.24e9 to
    7.52e9: the tokens' gradient, whose float32 and bf16 forms the plain
    gathers fused into themselves, is an operand of two loops and lies in
    HBM): under the 15.0 GiB ISSUE 39 set.  The kernel calls in the step are the ones the block kind
    counts FLOPs for (``moe_gmm_train_calls``, ``mla_flash_train_calls``): a
    roofline share must not credit a pass the program does not run."""
    kind = kinds.load("kimi_vl")
    doc, cfg = kinds.cell_doc("kimi_vl"), kinds.cell_cfg("kimi_vl")
    compiled, _, _ = _compiled_train_step(topo.devices[:1], cfg,
                                          kind.init_params, doc["train"])
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(
        12 * kind.num_params(doc), rel=1e-3)
    assert mem.alias_size_in_bytes > 0.999 * mem.output_size_in_bytes
    assert mem.peak_memory_in_bytes < 15.0 * 2**30, mem.peak_memory_in_bytes
    # a mesh of one device: nothing to gather.  0.94e9 under the
    # 11_878_587_904 PR 39 left: the flash kernels' operands, results and
    # saved ``attn_out`` at 192 and 128 lanes where all were 256 (PR 45);
    # 0.45e6 under PR 45's 10_939_999_232 with the backward one kernel
    # (PR 48: dq leaves the call that writes dk and dv); 0.13e6 over that
    # since PR 58: the step's new counters (a holder's load, the balance
    # term's zeros), 36 scalar instructions beside the parent's 8,669;
    # 0.69e9 over that since PR 59 (the docstring)
    assert mem.temp_size_in_bytes == 11_627_354_112
    assert mem.peak_memory_in_bytes == 15_534_652_928
    # the dense layer's pass is unrolled, the expert layers' a scan's body:
    # a kernel's calls in the text are its calls a layer, forward plus
    # backward, once for each
    text = compiled.as_text()
    assert not re.findall(r" (?:%s)(?:-start)?\(" % "|".join(COLLECTIVES),
                          text)
    calls = {name: len(re.findall(
        "%" + name + r"(?:\.\d+)? = [^\n]*" + KERNEL, text)) for name in (
            "moe_gmm", "moe_gmm_dx", "moe_gmm_dw", "flash_fwd", "flash_dkv",
            KERNEL_MOE_ROWS_BLANK)}
    want = dict(kind.moe_gmm_train_calls(doc))
    # the walk in takes its buffer from a call that writes nothing: once in
    # the forward and once in the replay (the rows' gradient is written over
    # ``ys``, and the walks out sum into token-sized buffers)
    want[KERNEL_MOE_ROWS_BLANK] = 2
    # the backward is one kernel, ``flash_dkv``, since PR 48.  The block
    # kind's dict still says ``flash_dq: 1``; only its ``flash_fwd`` count
    # feeds the FLOPs, so the roofline credits no pass the program does not
    # run, and the entry is a ``benchmark`` PR's to drop.
    flash_calls = kind.mla_flash_train_calls(doc)
    want.update({k: 2 * flash_calls[k] for k in ("flash_fwd", "flash_dkv")})
    assert calls == want and text.count(KERNEL) == sum(want.values())
    assert "flash_dq" not in text
    assert kind.moe_gmm_train_passes(doc) == 4
    # a latent head reaches the flash kernels at its own two widths: no
    # operand or result of theirs is padded to 256 lanes
    flash = re.findall(r"%flash_(?:fwd|dkv)(?:\.\d+)? = [^\n]*" + KERNEL
                       + r"[^\n]*", text)
    widths = {int(d) for line in flash for d in re.findall(
        r"bf16\[\d+,\d+,\d+,(\d+)\]", line)}
    assert widths == {cfg.qk_head_dim, cfg.v_head_dim} == {192, 128}
    # the backward kernel asks for the VMEM its shape rule gives: a head's
    # 8,192 rows of dq resident in float32 with their output block (16 MiB)
    # and the room of its blocks and products
    from ray_tpu.ops import flash_attention as fa
    asked = fa._bwd_vmem(doc["train"]["sequence_length"], 1, cfg.qk_head_dim,
                         jnp.bfloat16)["compiler_params"].vmem_limit_bytes
    assert asked == (16 << 20) + fa.BWD_VMEM_BLOCKS
    backward = [line for line in flash if "%flash_dkv" in line]
    assert len(backward) == 2 and all(
        '"size":"%d"' % asked in line for line in backward)


# ---- a window / full pattern with its experts exchanged over ep (PR 58)

@pytest.mark.timeout(240)
def test_ep_cell_step_fits_four_chips_with_its_exchange_counted(topo, as_tpu):
    """``mellum2-12b-a2.5b-train-l4`` as its cell runs it: 4 x 8,192 tokens
    over the four chips, 16 of 64 experts a chip and a quarter of every
    other leaf.  Reading 13.17e9 bytes at the program's peak, arguments
    included (sandbox compile, PR 58), 11.43e9 since PR 59 (the sorted
    layout's rows walked; the buffers of the walks begin where the walks
    do).  The kernel calls are the ones the
    block kind counts FLOPs for, the band's two among them by name; the
    exchange is collective-permutes (three hops of a block's tokens, choices
    and gates and three results back a layer, their transposes, the tokens'
    walk again in the replay) and nothing is an all-to-all; the head is
    gathered once a pass."""
    kind = kinds.load("mellum")
    doc, cfg = kinds.cell_doc("mellum"), kinds.cell_cfg("mellum")
    compiled, _, sh = _compiled_train_step(topo.devices, cfg,
                                           kind.init_params, doc["train"])
    mem = compiled.memory_analysis()
    per = kind.layer_matrix_params(doc)
    whole_a_chip = 4 * 16 * per["expert"]
    quartered = kind.num_params(doc) - 4 * whole_a_chip
    assert mem.argument_size_in_bytes == pytest.approx(
        12 * (whole_a_chip + quartered / 4), rel=2e-3)
    assert mem.alias_size_in_bytes > 0.999 * mem.output_size_in_bytes
    assert mem.peak_memory_in_bytes <= 13_173_300_736, (      # PR 58's
        mem.peak_memory_in_bytes)
    assert sh.params["blocks"]["experts"]["w_gate"].spec == P(
        None, "ep", None, None)
    text = compiled.as_text()
    calls = {name: len(re.findall(
        "%" + name + r"(?:\.\d+)? = [^\n]*" + KERNEL, text)) for name in (
            "moe_gmm", "moe_gmm_dx", "moe_gmm_dw", "flash_fwd", "flash_dkv",
            "flash_window_prefill", "flash_window_bwd",
            KERNEL_MOE_ROWS_BLANK)}
    want = {k: 4 * v for k, v in kind.moe_gmm_train_calls(doc).items()}
    # 4 layers x 4 ring steps x (the forward's walk in and the replay's)
    want[KERNEL_MOE_ROWS_BLANK] = 4 * 4 * 2
    want.update(flash_fwd=1, flash_dkv=1, flash_window_prefill=3,
                flash_window_bwd=3)
    assert calls == want and text.count(KERNEL) == sum(want.values())
    # nothing gathers, fills or selects over a block's whole sorted layout
    # (48 gathers of it a step to PR 58): the rows are moved a trip's
    # stretch at a time, 512 rows in and 256 tokens out, and 8,192 by token
    assert not re.findall(
        r"= bf16\[69632,2304\]\S* (?:gather|broadcast|select|transpose)\(",
        text)
    assert {int(n) for n in re.findall(
        r"= (?:bf16|f32)\[(\d+),2304\]\S* gather\(", text)} <= {
            256, 512, 8192, 32768}
    permutes = len(re.findall(r" collective-permute(?:-start)?\(", text))
    assert 4 * (12 + 9 + 9) <= permutes <= 4 * 32, permutes
    assert not re.findall(r" all-to-all(?:-start)?\(", text)
    _assert_head_gathered_once_a_pass(text, cfg, batch=4)
