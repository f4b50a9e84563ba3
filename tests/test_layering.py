"""Which unit of ``megatron_llm_tpu/`` may import which.

A unit is a subpackage, or one of the four top-level modules.  The table
below is written by hand: it is the package's import graph as it stands,
split into the edges that point down the layer order and the ones that do
not (``KNOWN_UPWARD``, each a named debt, ROADMAP D15).  An import that is
in neither fails its unit's case until someone edits the table on purpose,
and an edge or a debt that no import needs any more fails until it is struck.
Nothing in the package may import the benchmark, a test tier or a script
of the repo's root: the program does not know how it is measured.

Sources are parsed with ``ast``; nothing is imported, jax least of all.
"""

import ast
from pathlib import Path

import pytest

PACKAGE = Path(__file__).resolve().parents[1] / "megatron_llm_tpu"

# bottom to top: a unit may import the units before it
LAYERS = (
    "config", "utils", "obs", "analysis", "metrics", "resilience",
    "kernels", "parallel", "ops", "models", "checkpointing", "data",
    "tokenizer", "serving", "generation", "training", "tasks", "tools",
    "initialize",
)
RANK = {unit: i for i, unit in enumerate(LAYERS)}

DOWNWARD = {
    "config": (),
    "utils": (),
    "obs": (),
    # analysis/sanitizers.py reads the compilation records of
    # obs/compile.py (the recompilation guard, the watchdog's clock)
    "analysis": ("obs",),
    "metrics": ("analysis", "obs"),
    "resilience": ("analysis", "metrics"),
    "kernels": (),
    "parallel": ("config", "utils"),
    "ops": ("config", "kernels", "parallel"),
    "models": ("config", "kernels", "ops", "parallel"),
    "checkpointing": ("config", "metrics", "models", "resilience"),
    "data": ("utils",),
    "tokenizer": ("utils",),
    "serving": ("analysis", "config", "kernels", "models", "obs", "ops",
                "parallel", "resilience", "utils"),
    "generation": ("analysis", "config", "metrics", "models", "obs",
                   "serving", "tokenizer"),
    "training": ("checkpointing", "config", "data", "metrics", "models",
                 "obs", "ops", "parallel", "resilience", "utils"),
    "tasks": ("checkpointing", "config", "models", "parallel", "tokenizer",
              "training"),
    "tools": ("checkpointing", "config", "data", "generation", "models",
              "obs", "ops", "parallel", "tokenizer", "utils"),
    "initialize": (),
}

KNOWN_UPWARD = {
    # utils/timers.py feeds its spans to obs.trace: the timers are an
    # instrument and belong in obs
    ("utils", "obs"),
    # every obs module takes its lock from analysis.sanitizers.make_lock:
    # the tracked lock belongs below both
    ("obs", "analysis"),
    # parallel/pipeline.py and pipeline_encdec.py run the model's layers
    # (models.transformer, ops.cross_entropy), while models imports
    # parallel: the schedules belong above the model
    ("parallel", "ops"),
    ("parallel", "models"),
    # serving/engine.py samples with generation.sampling, while
    # generation/server.py imports serving
    ("serving", "generation"),
}

# the benchmark, the test tiers, and what lies at the repo's root
OUTSIDE = {
    "benchmarks", "tests", "tests_tpu", "tools", "examples", "chip_smoke",
    "finetune", "pretrain_bert", "pretrain_ict", "pretrain_t5",
    "__graft_entry__",
}


def _sources(unit):
    path = PACKAGE / unit
    return sorted(path.rglob("*.py")) if path.is_dir() \
        else [path.with_suffix(".py")]


def _imported_modules(source):
    """(absolute dotted module, line) for every import statement in
    ``source``, wherever it stands; ``from x import a`` also yields
    ``x.a``, which names a module when ``a`` is one."""
    here = source.relative_to(PACKAGE.parent).with_suffix("").parts[:-1]
    for node in ast.walk(ast.parse(source.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else ()
            module = ".".join(base + ((node.module,) if node.module else ()))
            yield module, node.lineno
            for alias in node.names:
                yield f"{module}.{alias.name}", node.lineno


@pytest.mark.parametrize("unit", LAYERS)
def test_unit_imports_only_what_the_table_allows(unit):
    on_disk = {p.stem for p in PACKAGE.iterdir()
               if p.name not in ("__init__.py", "__pycache__")
               and (p.is_dir() or p.suffix == ".py")}
    assert on_disk == set(LAYERS), "a unit without a row in the table"
    assert all(RANK[t] < RANK[unit] for t in DOWNWARD[unit]), \
        "DOWNWARD holds an upward edge: it belongs in KNOWN_UPWARD"
    assert all(RANK[s] < RANK[t] for s, t in KNOWN_UPWARD)

    allowed = set(DOWNWARD[unit]) | {t for s, t in KNOWN_UPWARD if s == unit}
    outside, unlisted = [], []
    for source in _sources(unit):
        where = source.relative_to(PACKAGE.parent)
        for module, line in _imported_modules(source):
            top, _, rest = module.partition(".")
            if top in OUTSIDE:
                outside.append(f"{where}:{line} imports {module}")
            target = rest.partition(".")[0]
            if top == PACKAGE.name and target in RANK \
                    and target != unit and target not in allowed:
                unlisted.append(f"{where}:{line} imports {module}")
    assert not outside, "the package imports its own measurement or tests"
    assert not unlisted, f"{unit} may import {sorted(allowed)}"


def _imports_of(unit):
    """The units of the package that ``unit``'s sources import."""
    found = set()
    for source in _sources(unit):
        for module, _line in _imported_modules(source):
            top, _, rest = module.partition(".")
            if top == PACKAGE.name:
                found.add(rest.partition(".")[0])
    return found & (set(RANK) - {unit})


def test_every_downward_edge_is_still_imported():
    """A row of ``DOWNWARD`` lists what its unit imports today, not what
    it once did: an edge nothing needs would let the import back in
    unseen."""
    unused = {unit: sorted(set(DOWNWARD[unit]) - _imports_of(unit))
              for unit in LAYERS}
    unused = {unit: edges for unit, edges in unused.items() if edges}
    assert not unused, f"no longer imported, strike from DOWNWARD: {unused}"


@pytest.mark.parametrize("pair", sorted(KNOWN_UPWARD), ids="->".join)
def test_every_known_upward_pair_is_still_needed(pair):
    """A repair removes its pair in the same change (ROADMAP D15): a pair
    that no import of its unit needs is a debt already paid."""
    unit, target = pair
    assert target in _imports_of(unit), \
        f"nothing in {unit} imports {target}: strike {pair} from KNOWN_UPWARD"
