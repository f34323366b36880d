"""One frozen spec for every attention entry point (DESIGN.md §14).

Before this module every attention entry threaded six parallel keywords
(``mode``, ``rescale``, ``kv_splits``, ``kv_dtype``, ``block``, ``scale``)
by hand, and speculative decoding adds two more (``spec_tokens``,
``spec_draft``).  :class:`AttnSpec` packs them into ONE frozen, hashable
dataclass that rides the jit cache as a single static argument.

Three invariants make the spec safe as a static jit key:

  1. **Resolution before the cache** — ``rescale=None`` (the process
     default) is resolved to a concrete mode string BEFORE the jitted
     function is looked up (:func:`canonicalize`), so flipping
     ``softmax_state.set_default_mode`` can never serve a stale trace.
     This preserves the contract ``jit_with_rescale`` established.
     ``use_kernels=None`` / ``interpret=None`` resolve from the backend
     the same way (:func:`resolve_backend`): compiled Pallas kernels on
     a TPU, XLA twins (and interpret mode, where a kernel is asked for)
     elsewhere.
  2. **Projection onto the entry's used fields** — every entry declares
     which spec fields its trace depends on (``uses``); all other fields
     are canonicalized to their defaults before keying the cache, so
     flipping an unused field (say ``spec_tokens`` on a decode kernel)
     never retraces (tests/test_softmax_state.py pins this).
  3. **Keyword shims** — the legacy keyword signature still works: the
     entry wrapper collects spec-field keywords, builds an
     :class:`AttnSpec`, and emits a ``DeprecationWarning``.  Passing both
     ``spec=`` and a legacy keyword is an error, never a silent merge.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax

from repro.kernels import softmax_state


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Frozen, jit-hashable attention configuration.

    ``scale`` is the softmax temperature (required in practice at kernel
    entries; the 0.0 default exists so model-level specs can be built
    before the per-layer scale is known).  ``rescale=None`` means "the
    process default" and is resolved before any jit lookup.
    ``kv_splits=None`` means auto-scheduled; the legacy ``n_splits``
    keyword aliases onto it.  ``use_kernels=None`` / ``interpret=None``
    mean "decided by the backend" (:func:`resolve_backend`).
    ``spec_tokens``/``spec_draft`` configure speculative decoding (0 =
    off) and are consumed by the serve loop and ``model.verify_step`` — no
    kernel trace depends on them."""
    scale: float = 0.0
    mode: str = "etap"             # attention pipeline: etap | standard
    rescale: str | None = None     # online-softmax mode (None = default)
    kv_splits: int | None = None   # split-KV count (None = auto)
    kv_dtype: str = "fp"           # paged pool storage layout
    block: int = 512               # dense KV block size
    use_kernels: bool | None = None  # Pallas kernels (None: on a TPU)
    interpret: bool | None = None    # Pallas interpret mode (None: off a TPU)
    spec_tokens: int = 0           # speculative draft length k (0 = off)
    spec_draft: str = "ngram"      # draft proposer: ngram | head

    def replace(self, **kw) -> "AttnSpec":
        return dataclasses.replace(self, **kw)


FIELDS = tuple(f.name for f in dataclasses.fields(AttnSpec))
_DEFAULTS = AttnSpec()
# legacy keyword spellings that map onto a differently-named spec field
LEGACY_ALIASES = {"n_splits": "kv_splits"}
LEGACY_KEYS = frozenset(FIELDS) | frozenset(LEGACY_ALIASES)


def coerce(spec: AttnSpec | None, legacy: dict, *,
           where: str = "attention entry") -> AttnSpec:
    """Build the effective spec from ``spec=`` or legacy keywords.

    ``legacy`` holds spec-field keywords collected from a call site that
    predates the spec API; a non-empty dict emits ``DeprecationWarning``
    and builds a fresh :class:`AttnSpec`.  Mixing both styles raises:
    silently merging a keyword into a caller-built spec would hide which
    one wins."""
    if legacy:
        if spec is not None:
            raise TypeError(
                f"{where}: got both spec= and legacy attention keyword(s) "
                f"{sorted(legacy)}; fold them into the AttnSpec")
        warnings.warn(
            f"{where}: attention keyword(s) {sorted(legacy)} are "
            f"deprecated; pass spec=AttnSpec(...) instead",
            DeprecationWarning, stacklevel=3)
        kw = {LEGACY_ALIASES.get(k, k): v for k, v in legacy.items()}
        return resolve_backend(AttnSpec(**kw))
    return resolve_backend(spec if spec is not None else AttnSpec())


def resolve_backend(spec: AttnSpec) -> AttnSpec:
    """Fill the backend-dependent fields left at None.  On a TPU the
    Pallas kernels are the attention path and run compiled; on any other
    backend attention takes the XLA twins, and a kernel asked for
    explicitly runs in interpret mode.  Explicit values are kept."""
    if spec.use_kernels is not None and spec.interpret is not None:
        return spec
    tpu = jax.default_backend() == "tpu"
    return spec.replace(
        use_kernels=tpu if spec.use_kernels is None else spec.use_kernels,
        interpret=(not tpu) if spec.interpret is None else spec.interpret)


def split_legacy(kw: dict) -> dict:
    """Pop every spec-field keyword out of ``kw`` (mutated in place) and
    return them — the shim half of an entry wrapper."""
    return {k: kw.pop(k) for k in list(kw) if k in LEGACY_KEYS}


def project(spec: AttnSpec, uses) -> AttnSpec:
    """Canonicalize every field OUTSIDE ``uses`` to its default.

    The projected spec is what keys the jit cache: two specs differing
    only in fields an entry's trace ignores collapse to one cache entry,
    so flipping an unused knob never retraces (the stale-flip regression
    test).  ``scale`` is always kept."""
    keep = set(uses) | {"scale"}
    return AttnSpec(**{f: getattr(spec if f in keep else _DEFAULTS, f)
                       for f in FIELDS})


def canonicalize(spec: AttnSpec, uses) -> AttnSpec:
    """Project onto ``uses`` and resolve ``rescale`` and the backend
    fields to concrete values — the full pre-jit-cache normalization of an
    entry wrapper."""
    spec = project(spec, uses)
    return resolve_backend(
        spec.replace(rescale=softmax_state.resolve(spec.rescale)))


def attn_entry(*, uses=(), static_argnames=()):
    """Decorator for public attention entry points.

    The decorated function must take ``spec`` keyword-only; the wrapper
    accepts either ``spec=AttnSpec(...)`` or the legacy spec-field
    keywords (DeprecationWarning), canonicalizes (projection onto
    ``uses`` + rescale resolution) BEFORE the jit-cache lookup, and calls
    the jitted body with ``spec`` as a static argument.  Non-spec
    keywords (``k_sz``, ``combine``, ...) pass through untouched;
    ``static_argnames`` lists the non-spec statics among them."""
    def deco(fn):
        jfn = jax.jit(fn, static_argnames=("spec",) + tuple(static_argnames))

        @functools.wraps(fn)
        def wrapper(*args, spec=None, **kw):
            legacy = split_legacy(kw)
            s = coerce(spec, legacy, where=fn.__name__)
            s = canonicalize(s, uses)
            return jfn(*args, spec=s, **kw)

        wrapper.__wrapped_jit__ = jfn
        wrapper.__attn_uses__ = ("scale",) + tuple(uses)
        return wrapper
    return deco
