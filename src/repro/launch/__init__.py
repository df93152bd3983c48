"""Launchers: mesh construction, dry-run driver, train/serve entry points."""
import contextlib
import os
from pathlib import Path

#: Persistent compilation cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset.  A fixed path in the checkout: the directory is part of the
#: cache's key, so a path that moved between runs would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here; otherwise the cache goes to ``COMPILE_CACHE_DIR``.
    Call before the first compile."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def add_policy_args(ap) -> None:
    """Shared --policy / --site-policy CLI surface for the launchers."""
    ap.add_argument("--policy", default=None,
                    help="TCEC policy scoped over the whole run (any "
                         "registered name, e.g. bf16x6)")
    ap.add_argument("--site-policy", action="append", default=[],
                    metavar="SITE=POLICY",
                    help="per-site policy override (repeatable), e.g. "
                         "--site-policy lm_head=bf16x6 --site-policy "
                         "router=bf16x3")


def policy_scope_from_args(args):
    """Build the policy_scope the launcher flags describe (or a no-op)."""
    from repro.core.context import policy_scope
    overrides = {}
    for kv in args.site_policy:
        site, _, name = kv.partition("=")
        if not site or not name:
            raise SystemExit(f"--site-policy expects SITE=POLICY, got {kv!r}")
        overrides[site] = name
    if args.policy is None and not overrides:
        return contextlib.nullcontext()
    return policy_scope(args.policy, **overrides)
