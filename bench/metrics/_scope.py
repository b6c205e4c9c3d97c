"""Device time under a named scope of the program.

The chain names its layers with ``jax.named_scope`` (``bmf_u_step``,
``bmf_sample``, ...). The compiler keeps each instruction's scope path in
its ``op_name`` metadata. A TPU trace event names the instruction it ran
(its HLO text, without the metadata) and carries no ``op_name`` stat, so
the path is looked up in the optimized HLO of the executables alive in
this process: an event is matched to the instruction of the same name,
result shape and opcode in a module that holds a ``bmf_`` scope. An op is
under scope S when S is a whole component of its path; a component a
transform wraps (``vmap(S)``) counts as S.

Time under S is the union of the intervals of its ops on each chip the
cell used (nested events count once), the mean over those chips, per
``info[per]`` (sweeps or calls). A loop whose path holds no scope, such
as the sweep loop's ``while``, counts toward no scope even though it
encloses scoped ops. Nothing when no op is under S: a program without
the scopes, or a trace with no device op.
"""
import re

from bench import trace as TR

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
_AMBIGUOUS = object()


def hlo_paths(texts) -> dict:
    """{(instruction, result shape, opcode): op_name path} over the HLO
    module texts that hold a ``bmf_`` scope; a key two modules give
    different paths maps to nothing."""
    out = {}
    for text in texts:
        if "bmf_" not in text:
            continue
        for line in text.splitlines():
            m = _INSTR.match(line)
            p = _OP_NAME.search(line) if m else None
            if p:
                k = m.groups()
                out[k] = p.group(1) if out.get(k, p.group(1)) == p.group(1) \
                    else _AMBIGUOUS
    return {k: v for k, v in out.items() if v is not _AMBIGUOUS}


def live_hlo_texts():
    """Optimized HLO, with metadata, of every executable alive in this
    process (the chain's stays in its jit cache)."""
    import jax
    return [m.to_string() for ex in jax.devices()[0].client.live_executables()
            for m in ex.hlo_modules()]


def components(path: str) -> frozenset:
    return frozenset(_WRAPPED.sub(r"\1", c) for c in path.split("/"))


def _scopes_of(r) -> dict:
    """{event name: scope components} for the device ops of ``r``."""
    paths = hlo_paths(live_hlo_texts())
    names = {}
    for d in r.devices():
        for o in d.ops:
            if o.name not in names:
                m = _INSTR.match(o.name)
                p = paths.get(m.groups()) if m else None
                names[o.name] = components(p) if p else frozenset()
    return names


def scope_ms(r, scope: str, per: str = "sweeps"):
    devs = r.devices()
    n = r.info.get(per)
    if not devs or not n or not any(d.ops for d in devs):
        return None
    names = _scopes_of(r)
    ns = [TR.busy_ns([o for o in d.ops if scope in names[o.name]])
          for d in devs]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / n / 1e6
