"""CI workflow self-consistency checks.

Round-2 and round-3 reviews both caught `.github/workflows/ci.yaml`
shipping a pip list that could not run the test suite (orbax-checkpoint
was missing while models/checkpoint.py lazily imports orbax at runtime).
This test makes that failure mode structural: it parses the workflow's
`pip install` line and asserts it covers every third-party import
reachable from the suite, so the list can only drift if this test is
updated with it.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
WORKFLOW = REPO / ".github" / "workflows" / "ci.yaml"

# import name -> pip distribution installed by ci.yaml.
IMPORT_TO_DIST = {
    "jax": "jax",
    "jaxlib": "jax",  # jax[cpu] pulls jaxlib
    "numpy": "numpy",
    "msgpack": "msgpack",
    "zmq": "pyzmq",
    "grpc": "grpcio",
    "google": "protobuf",  # google.protobuf
    "prometheus_client": "prometheus-client",
    "transformers": "transformers",
    "huggingface_hub": "transformers",  # hard dependency of transformers
    "tokenizers": "tokenizers",
    "xxhash": "xxhash",
    "ml_dtypes": "ml_dtypes",
    "optax": "optax",
    "orbax": "orbax-checkpoint",
    "yaml": "pyyaml",
    "pytest": "pytest",
    "flake8": "flake8",
}

# Soft-imported integrations the suite skips when absent; CI
# intentionally does not install them.
OPTIONAL_IMPORTS = {
    "torch",  # test_vllm_spec.py gates on pytest.importorskip("torch")
    "vllm",  # offload/vllm_spec.py degrades to stand-in ABCs
    "modelscope",  # services/uds_tokenizer.py: alt hub, gated import
    "flax",
    "chex",
    "einops",
}

LOCAL_TOP_LEVELS = {
    "llm_d_kv_cache_manager_tpu",
    "tests",
    "examples",
    "hack",
    "render_chart",  # hack/render_chart.py imported by test_chart.py
    "helpers",  # tests/helpers, sys.path'd by profiling scripts
    "benchmarks",  # chip_smoke.py stands on the harness
    "chip_smoke",
    "__graft_entry__",
}


def _workflow_pip_list() -> set:
    text = WORKFLOW.read_text()
    match = re.search(
        r"pip install (.*?)\n\s*- name:", text, flags=re.DOTALL
    )
    assert match, "could not locate the pip install step in ci.yaml"
    tokens = match.group(1).replace("\\\n", " ").split()
    dists = set()
    for token in tokens:
        token = token.strip().strip('"')
        if not token or token == "run:":
            continue
        dists.add(re.split(r"[\[=<>]", token)[0])
    return dists


def _imports_under(path: pathlib.Path, recursive: bool = True) -> set:
    names = set()
    for py in path.rglob("*.py") if recursive else path.glob("*.py"):
        try:
            tree = ast.parse(py.read_text())
        except SyntaxError:  # pragma: no cover - repo must parse
            raise AssertionError(f"unparsable file {py}")
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    names.add(alias.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                if node.module:
                    names.add(node.module.split(".")[0])
    return names


def test_pip_list_covers_all_required_imports():
    imports = set()
    for sub in ("llm_d_kv_cache_manager_tpu", "tests", "examples", "hack"):
        imports |= _imports_under(REPO / sub)
    # top-level scripts only (chip_smoke.py, __graft_entry__.py)
    imports |= _imports_under(REPO, recursive=False)

    stdlib = set(sys.stdlib_module_names)
    third_party = {
        name
        for name in imports
        if name not in stdlib
        and name not in LOCAL_TOP_LEVELS
        and name not in OPTIONAL_IMPORTS
    }

    unmapped = third_party - set(IMPORT_TO_DIST)
    assert not unmapped, (
        f"imports with no pip mapping: {sorted(unmapped)}; add them to "
        "IMPORT_TO_DIST *and* to ci.yaml's pip install list"
    )

    installed = _workflow_pip_list()
    missing = {
        IMPORT_TO_DIST[name]
        for name in third_party
        if IMPORT_TO_DIST[name] not in installed
    }
    assert not missing, (
        f"ci.yaml pip list is missing {sorted(missing)} — the workflow "
        "would fail at the pytest step"
    )


def test_workflow_has_native_format_gate():
    text = WORKFLOW.read_text()
    assert "clang-format" in text, (
        "ci.yaml must gate native/src formatting (reference "
        "ci-pr-checks.yaml runs clang-format)"
    )
    assert (REPO / ".clang-format").exists()


def test_optional_imports_are_really_optional():
    """Every OPTIONAL import must be absent from module import-time paths
    (only inside try/except or function bodies), so CI passes without
    them."""
    import importlib

    for module in (
        "llm_d_kv_cache_manager_tpu.offload.vllm_spec",
        "llm_d_kv_cache_manager_tpu.models.checkpoint",
        "llm_d_kv_cache_manager_tpu.services.uds_tokenizer",
    ):
        importlib.import_module(module)  # must not require optional deps
