"""Which phase of a device step each of its operations belongs to.

The served step programs wrap their phases in ``jax.named_scope``
(:data:`PHASES`); the compiler keeps the scope path of every operation in
the optimised HLO's ``metadata={op_name="jit(step)/colour/..."}``. A device
trace names operations by their HLO names (``fusion.24``), which any edit
to the step renumbers; joined to this module's map, a trace reads
"the CAVLC pack" and "the 4:2:0 subsampling" from PR to PR.

Built **on demand only** (the benchmark's reader after the server has
stopped, a debugging session): lowering and loading the 1080p H.264 step
from the compile cache takes ~15 s, which neither boot nor the served path
can pay.
"""

from __future__ import annotations

import logging
import re
import threading
from typing import Any, Dict, Optional

logger = logging.getLogger("selkies_tpu.observability.device_phases")

#: the scopes the step programs use, in the order a frame passes them
PHASES = ("colour", "damage", "motion", "transform", "entropy")
OTHER = "other"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
#: a scope path where a lowered module's text has it: a named location,
#: ``loc("jit(step)/colour/mul"(#loc3))``, and inside a ``shard_map`` the
#: path from there on, ``loc("vmap(colour)/mul"...)``; a file's location
#: (``loc("/a/colour/b.py":27:18)``) is none
_LOC_NAME = re.compile(r'loc\("([^"]*)"(?!:)')


def phase_of_op_name(op_name: str) -> Optional[str]:
    """``jit(step)/jit(main)/colour/mul`` -> ``colour``: the outermost
    component of the scope path that is a phase. A scope opened inside a
    function that runs under ``jax.vmap`` (a mesh lane's step runs the
    solo encode body so) reads ``vmap(colour)``."""
    for part in op_name.split("/"):
        while part.startswith("vmap(") and part.endswith(")"):
            part = part[5:-1]
        if part in PHASES:
            return part
    return None


def phase_map(hlo_text: str) -> Dict[str, str]:
    """{HLO operation name: phase} for every instruction of an optimised
    HLO module's text. A fusion carries its root's metadata, so it goes to
    its root's scope. An operation the compiler made, without metadata or
    with a name of its own and no scope path (a copy, a bitcast, the halves
    of an async pair, the pieces of a rewritten ``cumsum``), goes where the
    first of its operands that has a phase went, else where its first user
    went; with neither, and for an operation the program put in no scope:
    ``other``."""
    out: Dict[str, str] = {}
    operands: Dict[str, list] = {}
    bare = set()                  # made by the compiler: no metadata at all
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or "(" not in m.group(2):
            continue
        name, rest = m.group(1), m.group(2)
        operands[name] = _OPERAND.findall(rest.split(", metadata=")[0])
        meta = _OP_NAME.search(rest)
        if meta and "/" in meta.group(1):
            phase = phase_of_op_name(meta.group(1))
        else:
            # no metadata, or a name the compiler made up when it rewrote
            # an operation (``reduce_window_sum``): no scope path to read
            bare.add(name)
            phase = next((out[o] for o in operands[name]
                          if out.get(o, OTHER) != OTHER), None)
        out[name] = phase or OTHER
    # what is still unnamed takes its first user's phase: the copies and
    # slices the compiler puts between a parameter and its first real use
    first_user: Dict[str, str] = {}
    for name in reversed(list(out)):
        if out[name] == OTHER and name in bare:
            out[name] = first_user.get(name, OTHER)
        if out[name] != OTHER:
            for o in operands[name]:
                first_user[o] = out[name]
    return out


def base_encoder(encoder) -> Any:
    """The stripe encoder behind the server's wrappers (async driver ->
    pipeline -> base)."""
    seen = 0
    while seen < 4 and not hasattr(encoder, "lower_step"):
        nxt = getattr(encoder, "pipe", None) or getattr(encoder, "base", None)
        if nxt is None:
            break
        encoder, seen = nxt, seen + 1
    return encoder


def phases_named(text: str) -> set:
    """The phases that any scope path in ``text`` names: a lowered
    module's text with its locations, or an optimised module's with its
    ``op_name`` metadata (the operations inside fusions included)."""
    paths = _OP_NAME.findall(text) + _LOC_NAME.findall(text)
    return {p for p in map(phase_of_op_name, paths) if p is not None}


def _compiled_text(lower) -> str:
    """The optimised HLO of ``lower()``, with scope names that are the
    program's own. The persistent compile cache leaves metadata out of its
    key, and scopes are metadata: after an edit that only renames or adds
    scopes it hands back the executable of the tree that compiled the
    step first, which names that tree's phases (PR 35 read a lane's
    ``entropy`` as 0.0 ms so). Where the lowered program names a phase
    that nothing in the loaded executable does, the step is compiled once
    more under a key that holds the metadata: a cold compile, kept in the
    cache under that key for the next reader of the same tree."""
    lowered = lower()
    text = lowered.compile().as_text()
    missing = phases_named(lowered.as_text(debug_info=True)) \
        - phases_named(text)
    if not missing:
        return text
    logger.warning(
        "the loaded step names no %s, which its source does: another "
        "tree's executable from the compile cache; compiling it again "
        "with the metadata in the key", sorted(missing))
    import jax
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    # the lowering and its executable are memoised in the process too
    jax.clear_caches()
    jax.config.update(flag, True)
    try:
        return lower().compile().as_text()
    finally:
        jax.config.update(flag, before)


def step_phases(encoder, timeout_s: float = 120.0
                ) -> Optional[Dict[str, str]]:
    """The phase map of the step program ``encoder`` serves with (anything
    :func:`base_encoder` finds a ``lower_step()`` behind). The compile runs
    on a thread of its own and is given ``timeout_s``: from the cache it
    loads in seconds, and a caller after a served run must not sit through
    a cold compile of minutes if the key should differ, or if the cache
    held another tree's scope names (:func:`_compiled_text`). None then,
    and where the encoder has no ``lower_step``."""
    base = base_encoder(encoder)
    lower = getattr(base, "lower_step", None)
    if lower is None:
        return None
    box: Dict[str, Any] = {}

    def work() -> None:
        try:
            box["map"] = phase_map(_compiled_text(lower))
        except BaseException as e:
            box["error"] = e

    t = threading.Thread(target=work, name="device-phases", daemon=True)
    t.start()
    t.join(timeout_s)
    if "error" in box:
        raise box["error"]
    return box.get("map")
