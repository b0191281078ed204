"""CLI for the repro.analysis static-analysis suite.

::

    python -m repro.analysis lint            # repo-specific AST lint
    python -m repro.analysis kvsan           # clean lifecycle under shadow
    python -m repro.analysis jaxpr [--int8]  # step-program contract audit
    python -m repro.analysis types           # mypy (skipped if absent)
    python -m repro.analysis all             # lint + kvsan + jaxpr

Exit status is nonzero iff a violation was found, so CI can gate on it
directly. ``--mutate <id>`` seeds one known defect before running — the
command must then exit nonzero (that's the analyzer detecting the
mutation), which tests/test_analysis.py asserts for every registered id;
``--list-mutations`` prints the registry."""
from __future__ import annotations

import argparse
import sys
from typing import Callable, Dict

import numpy as np


def _fail(msg: str) -> int:
    print(msg)
    return 1


# --------------------------------------------------------------------- lint
def _lint_mutants() -> Dict[str, Dict[str, str]]:
    """Each lint mutation is an in-memory source tree that violates exactly
    one rule (the file paths select which rules apply)."""
    return {
        "lint-layering": {
            "core/scheduler.py": "import jax\n\ndef plan():\n    return []\n",
        },
        "lint-pad": {
            "serving/batcher.py": (
                "def assemble(pool, ids, width):\n"
                "    rows = pool.table_array(ids, width)\n"
                "    return rows.sum()\n"
            ),
        },
        "lint-determinism": {
            "serving/control_plane.py": (
                "import time\n\n"
                "def build_plan(state):\n"
                "    return (state, time.time())\n"
            ),
        },
        "lint-prng": {
            "serving/device_runner.py": (
                "import jax\n\n"
                "def dispatch(key, plan):\n"
                "    key, sub = jax.random.split(key)\n"
                "    sub2 = jax.random.split(sub)\n"
                "    return key, sub2\n"
            ),
        },
    }


def cmd_lint(args) -> int:
    from repro.analysis.lint import run_lint

    sources = _lint_mutants()[args.mutate] if args.mutate else None
    violations = run_lint(sources=sources)
    for v in violations:
        print(v)
    print(f"lint: {len(violations)} violation(s)")
    return 1 if violations else 0


# -------------------------------------------------------------------- kvsan
def _mk_pool(sanitizer, n_blocks=8, warm=False):
    from repro.serving.paged_cache import PagedPool

    return PagedPool(n_blocks=n_blocks, block_size=4, sanitizer=sanitizer,
                     keep_on_release=(lambda b: True) if warm else None)


def _mk_store(sanitizer, n_blocks=8):
    from repro.serving.host_tier import HostBlockStore

    store = HostBlockStore((1, 4, 1, 2), np.float32, n_blocks=n_blocks)
    store.sanitizer = sanitizer
    return store


def _blockish(n=1):
    return np.zeros((1, n, 4, 1, 2), np.float32)


def _kv_use_after_free(san) -> None:
    pool = _mk_pool(san)
    blocks = pool.allocate(1, 8)
    pool.free(1)                      # blocks return to the free list
    pool.share(2, blocks[0])          # sharing a freed block


def _kv_double_free(san) -> None:
    pool = _mk_pool(san)
    blocks = pool.allocate(1, 4)
    pool.free(1)
    pool.tables[1] = [blocks[0]]      # stale table resurrects the chain
    pool.free(1)                      # second release of the same block


def _kv_refcount_underflow(san) -> None:
    pool = _mk_pool(san, warm=True)
    blocks = pool.allocate(1, 4)
    pool.free(1)                      # block parks WARM (prefix cache)
    pool.tables[1] = [blocks[0]]
    pool.free(1)                      # releasing a WARM block: refs go < 0


def _kv_fill_before_reserve(san) -> None:
    store = _mk_store(san)
    store.fill_seq(("eng", 7), _blockish(), _blockish())  # never reserved


def _kv_cross_tier_aliasing(san) -> None:
    store = _mk_store(san)
    store.put(b"prefix-key", _blockish()[:, 0], _blockish()[:, 0])
    keyed_slot = store._by_key[b"prefix-key"]
    store._take_slot = lambda: keyed_slot   # allocator bug: hands out a keyed slot
    store.reserve_seq(("eng", 1), 1)


def _kv_swap_order(san) -> None:
    from repro.serving.control_plane import CopyEngine

    store = _mk_store(san)
    ce = CopyEngine()
    ce.sanitizer = san
    tag = ("eng", 1)
    store.reserve_seq(tag, 1)
    ce.submit(lambda: store.fill_seq(tag, _blockish(), _blockish()), tag=tag)
    store.restore_seq(tag)            # read ahead of the deferred fill


_KVSAN_MUTANTS: Dict[str, Callable] = {
    "kvsan-use-after-free": _kv_use_after_free,
    "kvsan-double-free": _kv_double_free,
    "kvsan-refcount-underflow": _kv_refcount_underflow,
    "kvsan-fill-before-reserve": _kv_fill_before_reserve,
    "kvsan-cross-tier-aliasing": _kv_cross_tier_aliasing,
    "kvsan-swap-order": _kv_swap_order,
}


def cmd_kvsan(args) -> int:
    from repro.analysis.kvsan import KVSanError, KVSanitizer
    from repro.serving.control_plane import CopyEngine

    san = KVSanitizer()
    if args.mutate:
        try:
            _KVSAN_MUTANTS[args.mutate](san)
        except KVSanError as e:
            print(e)
            print(f"kvsan: mutation {args.mutate!r} detected")
            return 1
        print(f"kvsan: mutation {args.mutate!r} NOT detected")
        return 0

    # clean lifecycle: device alloc/share/free, warm cache, host demote/
    # promote, reserve/fill via the copy engine, restore — zero violations
    pool = _mk_pool(san, warm=True)
    store = _mk_store(san)
    ce = CopyEngine()
    ce.sanitizer = san
    blocks = pool.allocate(1, 16)
    pool.share(2, blocks[0])
    pool.free(1)
    pool.free(2)
    store.put(b"k0", _blockish()[:, 0], _blockish()[:, 0], owner="e0")
    store.read([b"k0"], owner="e1")
    tag = ("e0", 42)
    store.reserve_seq(tag, 2)
    ce.submit(lambda: store.fill_seq(tag, _blockish(2), _blockish(2)), tag=tag)
    ce.sync(tag)
    store.restore_seq(tag)
    san.audit_host(store)
    stats = san.stats()
    print(f"kvsan: {stats['ops']} ops checked, "
          f"{stats['violations']} violation(s)")
    return 1 if stats["violations"] else 0


# -------------------------------------------------------------------- jaxpr
def _smoke_engine(arch: str, **kw):
    from repro.configs import get_arch, smoke_variant
    from repro.serving.engine import GenerationEngine

    return GenerationEngine(smoke_variant(get_arch(arch)), max_batch=2,
                            max_seq=64, prefill_chunk_size=16,
                            token_budget=20, **kw)


def _patch_pool_program(eng, wrap):
    """Replace the engine's bare pool-roundtrip program with a wrapped one
    (mutation helper: the wrapper injects the defect)."""
    import jax

    orig = eng.step_program

    def patched(which):
        jitted, pargs = orig(which)
        if which == "pool":
            return jax.jit(wrap(jitted)), pargs
        return jitted, pargs

    eng.step_program = patched


def _jx_collective(eng) -> None:
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))

    def wrap(jitted):
        def bad(k_pool, *rest):
            out, view = jitted(k_pool, *rest)
            # an explicit collective sneaks into the pool roundtrip
            s = shard_map(lambda a: jax.lax.psum(a, "model"), mesh=mesh,
                          in_specs=P(), out_specs=P())(view.sum())
            return out + 0 * s.astype(out.dtype), view
        return bad

    _patch_pool_program(eng, wrap)


def _jx_callback(eng) -> None:
    import jax
    import jax.numpy as jnp

    def wrap(jitted):
        def bad(k_pool, *rest):
            out, view = jitted(k_pool, *rest)
            # a host round-trip inside the step program
            s = jax.pure_callback(
                lambda x: np.asarray(x, np.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
                view.sum().astype(jnp.float32))
            return out + 0 * s.astype(out.dtype), view
        return bad

    _patch_pool_program(eng, wrap)


_JAXPR_ENGINE_MUTANTS: Dict[str, Callable] = {
    "jaxpr-collective": _jx_collective,
    "jaxpr-callback": _jx_callback,
}


def cmd_jaxpr(args) -> int:
    from repro.analysis.jaxpr_audit import (
        StepContract, audit_engine, default_contracts,
    )

    if args.mutate == "jaxpr-int8-upcast":
        # the gather-oracle decode dequantizes in XLA: holding it to the
        # in-kernel contract is the seeded violation
        eng = _smoke_engine(args.arch, kv_dtype="int8", kernel="pallas")
        report = audit_engine(eng, contracts=[StepContract(
            "decode_ref", max_all_reduce=0, require_int8_kernel_path=True)])
    elif args.mutate == "jaxpr-cache-buckets":
        import jax.numpy as jnp

        eng = _smoke_engine(args.arch)
        eng.warmup_step_variants()
        # mint an off-bucket packed length: one silent extra compile
        jitted, a = eng.step_program("fused_ragged")
        T = a[6].shape[0] + eng.pack_align
        flat = jnp.zeros((T,), jnp.int32)
        jitted(*a[:6], flat, flat, flat, flat, flat, flat, a[12])
        report = audit_engine(eng, contracts=[])
    elif args.mutate in _JAXPR_ENGINE_MUTANTS:
        eng = _smoke_engine(args.arch)
        _JAXPR_ENGINE_MUTANTS[args.mutate](eng)
        report = audit_engine(eng, contracts=[
            c for c in default_contracts(eng) if c.program == "pool"])
    elif args.mutate:
        return _fail(f"unknown jaxpr mutation {args.mutate!r}")
    else:
        kw = ({"kv_dtype": "int8", "kernel": "pallas"} if args.int8 else {})
        eng = _smoke_engine(args.arch, **kw)
        report = audit_engine(eng)
    print(report.render())
    return 0 if report.ok else 1


# -------------------------------------------------------------------- types
def cmd_types(args) -> int:
    """mypy over serving/ + analysis/ against the pinned mypy.ini baseline.
    The container may not ship mypy — CI installs it from requirements.txt;
    locally we skip (exit 0) rather than fail on a missing tool."""
    import subprocess
    from pathlib import Path

    try:
        import mypy  # noqa: F401
    except ImportError:
        print("types: mypy not installed; skipping (CI runs this)")
        return 0
    root = Path(__file__).resolve().parents[3]
    proc = subprocess.run(
        [sys.executable, "-m", "mypy", "--config-file", str(root / "mypy.ini"),
         str(root / "src/repro/serving"), str(root / "src/repro/analysis")],
        cwd=root)
    return proc.returncode


# ---------------------------------------------------------------------- all
def cmd_all(args) -> int:
    rc = 0
    for sub in (cmd_lint, cmd_kvsan, cmd_jaxpr):
        rc |= sub(args)
    return rc


def all_mutations() -> Dict[str, str]:
    """mutation id -> subcommand that hosts it (the test matrix)."""
    out = {m: "lint" for m in _lint_mutants()}
    out.update({m: "kvsan" for m in _KVSAN_MUTANTS})
    out.update({m: "jaxpr" for m in _JAXPR_ENGINE_MUTANTS})
    out.update({"jaxpr-int8-upcast": "jaxpr", "jaxpr-cache-buckets": "jaxpr"})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static-analysis suite: lint, kv sanitizer, jaxpr audit")
    ap.add_argument("command", nargs="?", default="all",
                    choices=["lint", "kvsan", "jaxpr", "types", "all"])
    ap.add_argument("--mutate", default=None, metavar="ID",
                    help="seed a registered defect; the run must exit nonzero")
    ap.add_argument("--list-mutations", action="store_true")
    ap.add_argument("--arch", default="smollm-135m",
                    help="architecture for the jaxpr audit engine")
    ap.add_argument("--int8", action="store_true",
                    help="audit the int8+pallas engine variant")
    args = ap.parse_args(argv)
    if args.list_mutations:
        for mid, sub in sorted(all_mutations().items()):
            print(f"{mid}  ({sub})")
        return 0
    if args.mutate and all_mutations().get(args.mutate) != args.command:
        return _fail(f"mutation {args.mutate!r} belongs to "
                     f"{all_mutations().get(args.mutate)!r}, "
                     f"not {args.command!r}")
    return {"lint": cmd_lint, "kvsan": cmd_kvsan, "jaxpr": cmd_jaxpr,
            "types": cmd_types, "all": cmd_all}[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
