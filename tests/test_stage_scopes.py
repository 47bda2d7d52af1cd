"""Every stage of the tick carries its ``laminar.<stage>`` scope into the
compiled program, and the scopes change nothing but metadata.

A tiny flat engine chunk is compiled on the CPU; its optimized HLO's
``op_name`` metadata names the stages it holds. The same chunk built with
``jax.named_scope`` patched out must lower to the same StableHLO and compile
to the same program once the metadata and the debug tables it points into
are stripped. HLO instruction names are derived from each op's scoped name,
so their numbers shift: the programs are compared up to a consistent
renaming.
"""

from __future__ import annotations

import contextlib
import re

import jax
import pytest

from repro.core import SCENARIOS, LaminarConfig, LaminarEngine, MemoryConfig
from repro.core.engine import SCOPE_PREFIX, STAGES

OP_NAME = re.compile(r'op_name="([^"]*)"')
METADATA = re.compile(r',? ?metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
INSTRUCTION = re.compile(r"\b([A-Za-z_][\w-]*)\.(\d+)\b")

BASE = dict(num_nodes=64, zone_size=32, probe_capacity=256,
            max_arrivals_per_tick=128, horizon_ms=1.0)
ALWAYS = {"completions", "dissemination", "intake", "addressing", "arbitration"}
CASES = {
    "jnp-nomem": (dict(), ALWAYS),
    "jnp-mem": (dict(memory=MemoryConfig(enabled=True), airlock=True),
                ALWAYS | {"survival"}),
    "pallas-nomem": (dict(use_pallas=True), ALWAYS),
    "pallas-mem": (dict(memory=MemoryConfig(enabled=True), airlock=True, use_pallas=True),
                   ALWAYS | {"survival"}),
    "jnp-mem-storm": (dict(memory=MemoryConfig(enabled=True), airlock=True,
                           scenario=SCENARIOS["storm"]),
                      ALWAYS | {"survival", "disruption"}),
}


def chunk_programs(cfg: LaminarConfig) -> tuple[str, str]:
    """(StableHLO, optimized HLO) text of the engine's one-tick chunk program."""
    engine = LaminarEngine(cfg)
    s, lam = engine.init(0)
    lowered = engine._runner(lam, 1).lower(s)
    return lowered.as_text(), lowered.compile().as_text()


def without_metadata(text: str) -> str:
    """The program alone: ``metadata={...}`` and the debug tables dropped,
    instructions renumbered in order of first appearance."""
    out, in_table = [], False
    for line in text.splitlines():
        if line in DEBUG_TABLES:
            in_table = True
        elif in_table:
            in_table = bool(line.strip())
        else:
            out.append(METADATA.sub("", line))
    seen: dict = {}
    return INSTRUCTION.sub(
        lambda m: seen.setdefault(m.group(0), f"{m.group(1)}.{len(seen)}"),
        "\n".join(out))


def stage_components(op_name: str) -> list[str]:
    return [c for c in op_name.split("/") if c.startswith(SCOPE_PREFIX)]


@pytest.fixture(scope="module")
def compiled():
    """``{case: (scoped, unscoped)}`` chunk programs, each ``chunk_programs``,
    built on first use."""
    cache: dict = {}

    def get(case: str):
        if case not in cache:
            cfg = LaminarConfig(**BASE, **CASES[case][0])
            scoped = chunk_programs(cfg)
            real = jax.named_scope
            jax.named_scope = lambda name: contextlib.nullcontext()
            try:
                bare = chunk_programs(cfg)
            finally:
                jax.named_scope = real
            cache[case] = (scoped, bare)
        return cache[case]

    return get


@pytest.mark.parametrize("case", sorted(CASES))
def test_stages_found_are_the_config_s_stages(compiled, case):
    scoped = compiled(case)[0][1]
    found = {c[len(SCOPE_PREFIX):] for n in OP_NAME.findall(scoped)
             for c in stage_components(n)}
    assert found == CASES[case][1]


@pytest.mark.parametrize("case", sorted(CASES))
def test_no_op_carries_two_stages(compiled, case):
    scoped = compiled(case)[0][1]
    names = OP_NAME.findall(scoped)
    assert names
    assert [n for n in names if len(stage_components(n)) > 1] == []


@pytest.mark.parametrize("case", sorted(CASES))
def test_scopes_change_only_metadata(compiled, case):
    (scoped_ir, scoped), (bare_ir, bare) = compiled(case)
    assert not any(SCOPE_PREFIX in n for n in OP_NAME.findall(bare))
    assert scoped_ir == bare_ir
    assert without_metadata(scoped) == without_metadata(bare)


def test_metadata_stripping_keeps_the_program():
    text = ("HloModule m\n\nFileNames\n1 \"a.py\"\n\n"
            "ENTRY %main.9 {\n"
            '  %add.7 = f32[4]{0} add(%p.3, %p.3), metadata={op_name="jit(f)/x{y}" '
            "stack_frame_id=3}\n}\n")
    assert without_metadata(text) == (
        "HloModule m\n\nENTRY %main.0 {\n  %add.1 = f32[4]{0} add(%p.2, %p.2)\n}")
    assert without_metadata(text.replace("add.7", "add.8")) == without_metadata(text)
    assert without_metadata(text.replace("f32[4]", "f32[5]")) != without_metadata(text)
