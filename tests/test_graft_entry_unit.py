"""Driver-entry plumbing that must not regress silently: the
machine-fingerprinted compile-cache key and the dryrun case registry's
structural invariants (round-5 redesign — see __graft_entry__ docstring
for the rc=124 history these encode)."""

import os
import subprocess
import sys

from __graft_entry__ import _CASES, machine_cache_dir


def test_machine_cache_dir_is_deterministic_and_keyed():
    a = machine_cache_dir("/tmp/base")
    b = machine_cache_dir("/tmp/base")
    assert a == b, "fingerprint must be stable within a machine"
    assert a.startswith("/tmp/base" + os.sep)
    leaf = os.path.basename(a)
    assert len(leaf) == 12 and all(c in "0123456789abcdef" for c in leaf)
    # a different base relocates, same fingerprint
    assert os.path.basename(machine_cache_dir("/tmp/other")) == leaf


def test_machine_cache_dir_never_keys_on_the_host_name(monkeypatch):
    # a sealed machine gets a fresh host name every run: with cpuinfo
    # unreadable the key falls back to the processor string, not the node
    import builtins
    import platform

    real_open = builtins.open

    def no_cpuinfo(path, *a, **k):
        if path == "/proc/cpuinfo":
            raise OSError("masked")
        return real_open(path, *a, **k)

    monkeypatch.setattr(builtins, "open", no_cpuinfo)
    monkeypatch.setattr(platform, "node", lambda: "host-a")
    a = machine_cache_dir("/tmp/base")
    monkeypatch.setattr(platform, "node", lambda: "host-b")
    assert machine_cache_dir("/tmp/base") == a


def test_import_sets_no_compile_cache():
    # the import-time setter is gone: entry() / dryrun_multichip() place
    # the cache through setup_compile_cache like every other entry point
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax, __graft_entry__\n"
         "assert not jax.config.jax_compilation_cache_dir\n"
         "from jax._src import xla_bridge\n"
         "assert not xla_bridge.backends_are_initialized()\n"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_case_registry_invariants():
    names = [c[0] for c in _CASES]
    assert len(set(names)) == len(names)
    # flat_dp must stay first: it always runs (budget check exempts it)
    # and multislice asserts against its loss
    assert names[0] == "flat_dp"
    assert names.index("multislice") > 0
    for name, fn, min_dev, need_even, units in _CASES:
        assert callable(fn), name
        assert min_dev >= 1 and units > 0, name
    # priority order is the VERDICT-prescribed certification order
    assert names[1:3] == ["fpn_dp*sp", "mask_dp*tp"], names
