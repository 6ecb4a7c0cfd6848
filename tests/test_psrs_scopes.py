"""Names inside the device-tier PSRS program: every plan stage, the
collectives, the local sort and the k-way merge's phases are
``jax.named_scope`` scopes that reach the compiled program's ``op_name``
metadata (which the profiler reports as each operation's ``tf_op``), and
``trace=True`` runs the same jitted program as ``trace=False``."""

import re

import jax
import jax.monitoring as mon
import jax.numpy as jnp
import numpy as np

from repro.pems_apps import psrs, psrs_sort

_N, _V, _K = 2048, 8, 2
SCOPES = ("psrs.sort_sample", "psrs.local_sort", "psrs.pick_splitters",
          "psrs.partition", "psrs.bucket_offsets", "psrs.merge",
          "pems.gather", "pems.bcast", "pems.alltoallv",
          "kway_merge.splitters", "kway_merge.index", "kway_merge.gather",
          "kway_merge.tiles")


def _keys(seed=5):
    return np.random.default_rng(seed).integers(
        -2**31, 2**31 - 1, size=_N, dtype=np.int32)


def _program(trace: bool):
    _, program, _ = psrs._build(_V, _K, _N // _V, None, "explicit",
                                "direct", None, trace=trace)
    return program


def _compiled_text(program) -> str:
    x = jnp.asarray(_keys()).reshape(_V, _N // _V)
    return program.lower(x).compile().as_text()


def _scope_names(text: str) -> set:
    """Every name-stack component of the ``op_name`` metadata, unwrapped
    from transforms (``vmap(psrs.merge)`` -> ``psrs.merge``)."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        for comp in re.split(r"[/;]", path):
            while (m := re.fullmatch(r"\w+\((.*)\)", comp)) is not None:
                comp = m.group(1)
            out.add(comp)
    return out


def _without_metadata(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    # The debug tables (source files, frames) that metadata points into.
    return "\n\n".join(b for b in text.split("\n\n") if not b.lstrip()
                       .startswith(("FileNames", "FunctionNames",
                                    "FileLocations", "StackFrames")))


def test_device_program_names_every_scope_in_its_hlo():
    names = _scope_names(_compiled_text(_program(trace=False)))
    assert set(SCOPES) <= names, sorted(set(SCOPES) - names)
    # No scope name holds the profiler's separators.
    assert not any(":" in s or "/" in s for s in SCOPES)


def test_traced_device_tier_runs_the_same_jitted_program():
    plain, traced = _program(trace=False), _program(trace=True)
    for program in (plain, traced):
        assert hasattr(program, "lower"), "the device tier must stay jitted"
    plain_text, traced_text = _compiled_text(plain), _compiled_text(traced)
    assert _without_metadata(plain_text) == _without_metadata(traced_text)

    lowered = []

    def on_span(event, start, end, fun_name=None, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(fun_name)

    keys = _keys(9)
    mon.register_event_time_span_listener(on_span)
    try:
        out_plain = psrs_sort(keys, v=_V, k=_K)
        out_traced, pems = psrs_sort(keys, v=_V, k=_K, trace=True,
                                     return_pems=True)
    finally:
        mon.unregister_event_time_span_listener(on_span)
    np.testing.assert_array_equal(out_traced, out_plain)
    np.testing.assert_array_equal(out_plain, np.sort(keys))
    # Each call lowers the whole program once, traced or not.
    assert lowered.count("jit(program)") == 2, lowered
    # No span fired inside the jitted program: only the call spans.
    names = [ev[1] for ev in pems.tracer.events()]
    assert names == ["call:prepare", "call:dispatch", "call:wait",
                     "call:extract"]
