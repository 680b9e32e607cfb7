"""The packages of ``fleetx_tpu/`` import downward, by a table.

``MAY_IMPORT`` is the package-level import graph as it stands (every
``import`` / ``from … import`` statement, lazy ones inside functions
included), written from the lowest layer up: a package may import only
what its row names, and a row names only earlier rows. The edges
that point UP today are listed apart in ``KNOWN_UPWARD`` (ROADMAP D11). A
case fails on an edge neither table has, and on a ``KNOWN_UPWARD`` entry
that the code no longer has — so that list can only shrink.

``ast`` only: no jax import, well under a second.
"""

import ast
import functools
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.join(REPO, "fleetx_tpu")

#: below → may import. Order matters: a row may name only earlier rows.
MAY_IMPORT = {
    "utils": (),
    "optims": (),
    "observability": ("utils",),
    "resilience": ("utils", "observability"),
    "data": ("utils",),
    "tools": ("utils",),
    "parallel": ("utils",),
    "lint": ("parallel",),
    "ops": ("parallel",),
    "models": ("utils", "observability", "parallel", "ops"),
    "serving": ("utils", "observability", "resilience", "parallel", "ops",
                "models"),
    "core": ("utils", "optims", "observability", "resilience", "data",
             "parallel", "models"),
    "finetune": ("utils", "observability", "resilience", "parallel", "core"),
}

#: today's upward edges, each a debt (ROADMAP D11); delete a line when the
#: code no longer has the edge
KNOWN_UPWARD = {
    # D11: utils/config.py validates the Distributed, Serving and
    # Observability blocks by reaching into the packages that own them
    # (rules.MESH_AXES, auto_layout.suggest_layout, slo.validate_slo_block,
    # router.RouterConfig); utils/download.py retries and counts through
    # resilience.policy and observability.metrics
    "utils": ("parallel", "observability", "resilience", "serving"),
    # D11: flight.FlightRecorder.dump writes through
    # resilience.integrity.atomic_write
    "observability": ("resilience",),
    # D11: parallel/shardcheck.py builds the real task module
    # (models.build_module) and the serving pool (paged_cache.init_pool)
    "parallel": ("models", "serving"),
    # D11: models/__init__.py:build_module names the task modules of core
    # and finetune; each family's module.py subclasses core.module
    "models": ("core", "finetune"),
}

#: nothing under fleetx_tpu/ may import the benchmark, a tool or a driver
OUTSIDE = ("benchmarks", "tools", "bench", "chip_smoke")


def _imports_of(path: str, package: list[str]):
    """Absolute dotted names one source file imports (relative imports
    resolved against ``package``, the file's own package path)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[:len(package) - node.level + 1]
                base = ".".join(up + ([node.module] if node.module else []))
            # `from fleetx_tpu import serving`, `from . import metrics`
            for alias in node.names:
                yield f"{base}.{alias.name}"


@functools.lru_cache(maxsize=None)
def _graph():
    """(package → packages of fleetx_tpu it imports,
    package → OUTSIDE names it imports); parsed once a process, by the
    first case that asks."""
    inner = {p: set() for p in MAY_IMPORT}
    outer = {p: set() for p in MAY_IMPORT}
    for pkg in MAY_IMPORT:
        for dirpath, _, files in os.walk(os.path.join(ROOT, pkg)):
            rel = os.path.relpath(dirpath, REPO).split(os.sep)
            for name in files:
                if not name.endswith(".py"):
                    continue
                for dotted in _imports_of(os.path.join(dirpath, name), rel):
                    parts = dotted.split(".")
                    if parts[0] in OUTSIDE:
                        outer[pkg].add(parts[0])
                    if parts[0] == "fleetx_tpu" and len(parts) > 1 \
                            and parts[1] in MAY_IMPORT and parts[1] != pkg:
                        inner[pkg].add(parts[1])
    return inner, outer


def test_the_table_names_every_package_and_points_down():
    on_disk = {d for d in os.listdir(ROOT)
               if os.path.isfile(os.path.join(ROOT, d, "__init__.py"))}
    assert on_disk == set(MAY_IMPORT)
    order = list(MAY_IMPORT)
    for pkg, deps in MAY_IMPORT.items():
        above = [d for d in deps if order.index(d) >= order.index(pkg)]
        assert not above, f"MAY_IMPORT[{pkg!r}] names {above}: not below it"
    outer = {p: sorted(names) for p, names in _graph()[1].items() if names}
    assert not outer, f"imports from outside the package: {outer}"


@pytest.mark.parametrize("package", list(MAY_IMPORT))
def test_package_imports_only_what_the_table_allows(package):
    found = _graph()[0][package]
    upward = set(KNOWN_UPWARD.get(package, ()))
    new = found - set(MAY_IMPORT[package]) - upward
    assert not new, (
        f"fleetx_tpu/{package} imports {sorted(new)}: an edge the table "
        f"does not have. Move the code down, or pass the dependency in")
    gone = upward - found
    assert not gone, (
        f"fleetx_tpu/{package} no longer imports {sorted(gone)}: delete "
        f"the entry from KNOWN_UPWARD (ROADMAP D11)")
