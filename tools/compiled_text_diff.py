"""Do two checkouts compile the benchmark's programs to the same text?

    python tools/compiled_text_diff.py --parent DIR --change DIR \
        [--programs train-345m,train-1.3b-dp2tp2,serve-345m] [--out DIR]

For each checkout a child process imports THAT checkout's ``apex_tpu``
and ``examples/gpt_pretrain.py``, builds the programs the benchmark's
cells run at the cells' own sizes (read from the checkout's
``benchmarks/configs`` and ``benchmarks/traffic``), compiles them for a
DESCRIBED v5e (``jax.experimental.topologies``: the TPU compiler is
installed, no chip is attached, nothing runs) and writes
``compiled.as_text()`` with operation names and source locations taken
out: each instruction's ``metadata={...}``, the tables of files and
lines above the computations, and the locations inside a Mosaic
kernel's serialized body (the body is replaced by the hash of its MLIR
printed without them).  The parent process then compares the files
and gives each program one of three verdicts:

- ``EQUAL``: the same bytes.
- ``EQUAL BUT FOR INSTRUCTION NAMES``: the same once every ``%name.N``
  is replaced by its rank of first appearance.  XLA numbers an
  instruction when it creates it, so a change that hands it one
  operation fewer (a duplicate it used to CSE away, say) moves the
  suffixes and nothing else: opcodes, shapes, layouts, operands,
  attributes, fusions and the schedule's order are all compared.
- ``DIFFERENT``: a unified diff of the renamed texts is left beside
  them.

Exit code 0 unless a program is ``DIFFERENT`` (then 1).

The programs:

- ``train-345m``          ``jit_train_step``, gpt2-345m, pretrain-s1024-b16
- ``train-1.3b-dp2tp2``   ``jit_train_step``, cerebras-gpt-1.3b, dp2 x tp2
- ``serve-345m``          ``jit__prefill`` and ``jit__decode`` of
  ``GPTModel.decode_fns`` at the gpt2 serving cells' shapes
- ``serve-dsv32``, ``serve-trinity``, ``serve-xing4``   ``jit__decode``
  and every ``jit__chunk`` (one a context extent or bucket, written as
  ``jit__chunk@<extent>``) of the three expert models' ``decode_fns`` at
  their cells' shapes; a checkout without the model skips the program

The train step is the trainer's own: ``main(--steps 0)`` builds the
mesh, the model, the optimizer state and the jitted step, with the mesh
laid over the described devices and ``jax.device_put`` answering with
shapes (a described device holds no array).  Off the TPU the kernel
dispatchers resolve to XLA, so the child tells
``apex_tpu.utils.platform`` that the platform is ``tpu``: the text then
holds the Mosaic kernels the chip runs.  An equal text is not a chip
run and says nothing about time.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import difflib
import functools
import hashlib
import importlib.util
import json
import os
import re
import subprocess
import sys
import tempfile
from unittest import mock

PROGRAMS = {
    "train-345m": ("gpt2-345m", "pretrain-s1024-b16"),
    "train-1.3b-dp2tp2": ("cerebras-gpt-1.3b", "pretrain-s2048-b8-dp2tp2"),
    "serve-345m": ("gpt2-345m", "backlog-short-in-long-out"),
    "serve-dsv32": ("deepseek-v3.2-ep16-share", "backlog-longdoc-in-mid-out"),
    "serve-trinity": ("trinity-large-ep8-share",
                      "backlog-mixed-short-long-in-mid-out"),
    "serve-xing4": ("xing4.0-29b-a4b-depth6", "backlog-16k-in-mid-out"),
}

# an instruction's metadata={op_name="jit(f)/..." stack_frame_id=7}, and
# the tables of files, functions and lines those ids point into
_METADATA = re.compile(
    r',?\s*(?<![A-Za-z_])metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
_LOCATION_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
    r"(?:\d+ .*\n)*\n?", re.MULTILINE)
# a Mosaic kernel rides in its custom call as MLIR bytecode, base64, and
# that too names the files and lines it was traced from
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def _kernel_without_locations(match) -> str:
    from jaxlib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    module = ir.Module.parse(base64.b64decode(match.group(1)), ctx)
    asm = module.operation.get_asm(enable_debug_info=False)
    digest = hashlib.sha256(asm.encode()).hexdigest()
    return f'"body":"mlir without locations, sha256 {digest}"'


def strip_metadata(text: str) -> str:
    text = _METADATA.sub("", _LOCATION_TABLES.sub("", text))
    return _KERNEL_BODY.sub(_kernel_without_locations, text)


# ------------------------------------------------------------- the child
def _read(root, kind, name):
    with open(os.path.join(root, "benchmarks", kind, name + ".json")) as f:
        return json.load(f)


def _padded_vocab(vocab: int, tp: int) -> int:
    unit = 128 * tp                     # the program's Megatron rule
    return -(-vocab // unit) * unit


def _train_step_text(root, topo, cfg, tr):
    import jax
    import jax.numpy as jnp

    from apex_tpu.transformer import parallel_state

    tp, dp = int(tr["tp"]), int(tr["dp"])
    spec = importlib.util.spec_from_file_location(
        "gpt_pretrain", os.path.join(root, "examples", "gpt_pretrain.py"))
    trainer = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trainer)

    def shapes_on(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                jnp.shape(x), jnp.result_type(x), sharding=s),
            tree, shardings)

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    on_described = functools.partial(
        parallel_state.initialize_model_parallel,
        devices=topo.devices[:tp * dp])
    # the arguments benchmarks/runners/train.py gives the trainer
    with mock.patch.object(parallel_state, "initialize_model_parallel",
                           on_described), \
            mock.patch.object(jax, "device_put", shapes_on):
        out = trainer.main([
            "--tp", str(tp),
            "--vocab", str(_padded_vocab(cfg["vocab_size"], tp)),
            "--layers", str(cfg["n_layer"]), "--hidden", str(cfg["n_embd"]),
            "--heads", str(cfg["n_head"]), "--seq", str(tr["seq"]),
            "--opt-level", tr["opt_level"],
            "--micro-batch", str(tr["micro_batch"]),
            "--num-micro", str(tr["num_micro"]),
            "--steps", "0", "--log-every", "1000000"])
    state, batch = out["step_args"][:4], out["step_args"][4:]
    batch = [jax.ShapeDtypeStruct(b.shape, b.dtype) for b in batch]
    text = out["step"].lower(*state, *batch).compile().as_text()
    parallel_state.destroy_model_parallel()
    return {"jit_train_step": text}


def _serve_texts(root, topo, cfg, tr):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving.kv_cache import KVCacheConfig, init_pools
    from apex_tpu.serving.serve import init_carry
    from apex_tpu.transformer import parallel_state

    slots, page = int(tr["slots"]), int(tr["page_size"])
    pages_per_seq = int(tr["pages_per_seq"])
    max_prompt = int(tr["max_prompt_len"])
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=1, devices=topo.devices[:1])
    # the model and the cache of benchmarks/runners/serve.py
    model = GPTModel(GPTConfig(
        vocab_size=_padded_vocab(cfg["vocab_size"], 1),
        num_layers=cfg["n_layer"], hidden_size=cfg["n_embd"],
        num_attention_heads=cfg["n_head"], ffn_hidden_size=cfg["n_inner"],
        max_position_embeddings=cfg["n_positions"],
        layernorm_epsilon=cfg["layer_norm_epsilon"],
        policy=amp.initialize("O5").policy))
    ccfg = KVCacheConfig(
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        head_dim=cfg["n_embd"] // cfg["n_head"],
        num_pages=1 + slots * pages_per_seq, page_size=page,
        max_seqs=slots, pages_per_seq=pages_per_seq, dtype=jnp.bfloat16)

    def shapes_on(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    # decode_fns reads the weights' bytes, so it is given arrays (on the
    # host); the steps are then lowered with their shapes on the mesh
    params = model.init(jax.random.PRNGKey(0))
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=max_prompt,
                           weight_dtype=None)
    params = shapes_on(params, model.param_specs())
    pools = shapes_on(jax.eval_shape(lambda: init_pools(ccfg)),
                      fns.pool_specs)
    carry = jax.eval_shape(lambda: init_carry(slots))
    carry = shapes_on(carry, jax.tree.map(lambda _: P(), carry))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    texts = {
        "jit__prefill": fns.prefill_jit.lower(
            params, pools, i32(1, max_prompt), i32(), i32(pages_per_seq),
            key).compile().as_text(),
        "jit__decode": fns.decode_jit.lower(
            params, pools, carry, i32(slots, pages_per_seq)
        ).compile().as_text(),
    }
    parallel_state.destroy_model_parallel()
    return texts


def _expert_serve_texts(root, topo, cfg, tr):
    """The model and the cache of ``benchmarks/runners/serve_latent_moe``
    / ``serve_window_moe`` / ``serve_hyper_latent`` (the traffic file's
    ``runner``), from shapes alone: the steps close over no weight."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.serving.kv_cache import KVCacheConfig, init_pools
    from apex_tpu.serving.serve import init_carry
    from apex_tpu.transformer import parallel_state

    bf16 = jnp.bfloat16
    slots, page = int(tr["slots"]), int(tr["page_size"])
    pages_per_seq, C = int(tr["pages_per_seq"]), int(tr["prefill_chunk"])
    if tr["runner"] == "serve_window_moe":
        from apex_tpu.models.afmoe import AfmoeConfig, AfmoeModel

        model = AfmoeModel(AfmoeConfig.from_hf(
            cfg, num_experts=cfg["published"]["num_experts"],
            held_experts=tuple(cfg["held_experts"]), params_dtype=bf16))
        ccfg = KVCacheConfig.of_classes(
            model.cache_classes(slots=slots, pages_per_seq=pages_per_seq,
                                page_size=page, prefill_chunk=C),
            page_size=page, max_seqs=slots, dtype=bf16)
    else:
        if tr["runner"] == "serve_hyper_latent":
            from apex_tpu.models.xing4 import Xing4Config, Xing4Model

            mcfg = Xing4Config.from_hf(cfg, params_dtype=bf16)
            model = Xing4Model(mcfg)
        else:
            from apex_tpu.models.deepseek_v32 import (
                DeepSeekV32Config, DeepSeekV32Model,
            )

            mcfg = DeepSeekV32Config.from_hf(
                cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
                held_experts=tuple(cfg["held_experts"]), params_dtype=bf16)
            model = DeepSeekV32Model(mcfg)
        ccfg = KVCacheConfig(
            num_layers=mcfg.num_hidden_layers, num_heads=1,
            head_dim=mcfg.latent_dim, num_pages=1 + slots * pages_per_seq,
            page_size=page, max_seqs=slots, pages_per_seq=pages_per_seq,
            dtype=bf16, kind="latent", latent_dim=mcfg.latent_dim,
            index_dim=mcfg.index_head_dim)
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=1, devices=topo.devices[:1])
    fns = model.decode_fns(None, mesh, ccfg,
                           max_prompt_len=int(tr["max_prompt_len"]),
                           prefill_chunk=C)
    on_mesh = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P())), tree)
    params = on_mesh(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pools = on_mesh(jax.eval_shape(lambda: init_pools(ccfg)))
    carry = on_mesh(jax.eval_shape(lambda: dict(
        init_carry(slots), **fns.decode.carry_extras)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table_width = ccfg.table_columns[-1][1]
    texts = {"jit__decode": fns.decode_jit.lower(
        params, pools, carry, i32(slots, table_width)).compile().as_text()}
    # one chunk program a context extent: those the cell's prompts reach
    top = -(-int(tr["max_prompt_len"]) // C) * C
    extents = getattr(fns.chunk, "ctx_buckets", None) or sorted(
        {min(start + C, ccfg.max_len) for start in range(0, top, C)})
    for ctx_len in extents:
        texts[f"jit__chunk@{ctx_len}"] = fns.chunk_jit.lower(
            params, pools, i32(1, C), i32(), i32(), i32(), i32(table_width),
            key, ctx_len=ctx_len).compile().as_text()
    parallel_state.destroy_model_parallel()
    return texts


def emit(root: str, out_dir: str, programs) -> None:
    """Child: compile ``programs`` from the checkout at ``root`` and
    write their stripped texts into ``out_dir``."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    sys.path.insert(0, root)
    import jax
    from jax.experimental import topologies

    import apex_tpu
    from apex_tpu.utils import platform

    if not os.path.abspath(apex_tpu.__file__).startswith(
            os.path.abspath(root) + os.sep):
        raise SystemExit(f"apex_tpu came from {apex_tpu.__file__}, "
                         f"not from {root}")
    jax.config.update("jax_enable_compilation_cache", False)
    platform._current_platform = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    os.makedirs(out_dir, exist_ok=True)
    for program in programs:
        config, traffic = PROGRAMS[program]
        cfg = _read(root, "configs", config)
        tr = _read(root, "traffic", traffic)
        build = (_train_step_text if tr["kind"] == "train" else
                 _serve_texts if tr.get("runner", "serve") == "serve" else
                 _expert_serve_texts)
        try:
            texts = build(root, topo, cfg, tr)
        except ImportError as e:
            print(f"skipped {program}: {root} has no such model ({e})",
                  flush=True)
            continue
        for module, text in texts.items():
            head = text.split("\n", 1)[0]
            if not head.startswith(f"HloModule {module.split('@')[0]}"):
                raise SystemExit(f"{program}: expected a program named "
                                 f"{module}, the text opens with {head!r}")
            path = os.path.join(out_dir, f"{program}.{module}.txt")
            with open(path, "w") as f:
                f.write(strip_metadata(text))
            print(f"wrote {path}", flush=True)


# ------------------------------------------------------------ the parent
_NAME = re.compile(r"%([A-Za-z_][\w.\-]*)")
_SIGNATURE_PARAMETER = re.compile(r"(?<=[(\s])([A-Za-z_][\w.\-]*)(?=: )")


def without_names(text: str) -> str:
    """Every ``%name.N`` replaced by its rank of first appearance (the
    module docstring says why); what an instruction does is in its
    opcode, shapes, layouts, operands and attributes, which stay."""
    rank = {}
    for name in _NAME.findall(text):
        rank.setdefault(name, f"v{len(rank)}")
    text = _NAME.sub(lambda m: "%" + rank[m.group(1)], text)
    return _SIGNATURE_PARAMETER.sub(
        lambda m: rank.get(m.group(1), m.group(1)), text)


def compare(parent_dir: str, change_dir: str) -> bool:
    names = sorted(set(os.listdir(parent_dir)) | set(os.listdir(change_dir)))
    names = [n for n in names if n.endswith(".txt")]
    all_equal = True
    print(f"{'program':44s} {'lines':>7s} {'kernels':>7s}  "
          f"{'parent sha256':12s}  {'change sha256':12s}  verdict")
    for name in names:
        texts = []
        for d in (parent_dir, change_dir):
            path = os.path.join(d, name)
            if os.path.exists(path):
                with open(path) as f:
                    texts.append(f.read())
            else:                       # a program only one side built
                texts.append("")
        a, b = texts
        sha = [hashlib.sha256(t.encode()).hexdigest()[:12] for t in texts]
        verdict = "EQUAL" if a == b and a else "DIFFERENT"
        if verdict == "DIFFERENT" and a and b:
            a, b = without_names(a), without_names(b)
            if a == b:
                verdict = "EQUAL BUT FOR INSTRUCTION NAMES"
        all_equal &= verdict != "DIFFERENT"
        print(f"{name[:-4]:44s} {a.count(chr(10)):7d} "
              f"{a.count('tpu_custom_call'):7d}  {sha[0]}  {sha[1]}  "
              f"{verdict}")
        if verdict == "DIFFERENT":
            diff = list(difflib.unified_diff(
                a.splitlines(), b.splitlines(), "parent/" + name,
                "change/" + name, lineterm="", n=1))
            path = os.path.join(change_dir, name[:-4] + ".diff")
            with open(path, "w") as f:
                f.write("\n".join(diff))
            print(f"  {len(diff)} diff lines in {path}; the first:")
            for line in diff[:12]:
                print("  " + line[:200])
    return all_equal


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="checkout of the parent commit")
    ap.add_argument("--change", help="checkout of the change")
    ap.add_argument("--programs", default=",".join(PROGRAMS),
                    help="comma-separated, of: " + ", ".join(PROGRAMS))
    ap.add_argument("--out", default=None,
                    help="directory for the texts (default: a temporary "
                         "one, removed at the end)")
    ap.add_argument("--emit", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--root", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    programs = [p for p in args.programs.split(",") if p]
    unknown = [p for p in programs if p not in PROGRAMS]
    if unknown:
        ap.error(f"unknown programs {unknown}")
    if args.emit:
        emit(os.path.abspath(args.root), args.emit, programs)
        return 0
    if not (args.parent and args.change):
        ap.error("--parent and --change are both needed")
    with contextlib.ExitStack() as stack:
        out = args.out or stack.enter_context(tempfile.TemporaryDirectory())
        dirs = {}
        # one child at a time: a process keeps the TPU's library, and
        # its lock, until it exits
        for side in ("parent", "change"):
            dirs[side] = os.path.join(out, side)
            subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--emit",
                 dirs[side], "--root", getattr(args, side), "--programs",
                 ",".join(programs)],
                check=True, cwd=getattr(args, side),
                env={k: v for k, v in os.environ.items()
                     if k != "PYTHONPATH"})
        equal = compare(dirs["parent"], dirs["change"])
    print("no program's compiled text differs" if equal
          else "compiled texts DIFFER")
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
