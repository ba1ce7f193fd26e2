"""A seed's parameters are a contract: the benchmark's expert cells route by
the router a seed draws, so ``tokens_per_s`` there moves with ``init``
(PERF.md §2). Every decoder family's ``init(cfg, PRNGKey(0))`` on its
``*-tiny`` preset is held here, leaf by leaf, to constants recorded once:
the tree's keys, every leaf's shape and dtype, and a float64 checksum a
leaf that moves with any element and with their order.

How the constants were made: at commit 017364a (the parent of the PR that
moved ``init``'s loops into ``models/lm.py``), from that commit's tree,

    JAX_PLATFORMS=cpu PYTHONPATH=<that tree> \
        python tests/test_init_pinned.py > tests/init_pinned.json

``phi4flash``'s were recorded by the PR that added the family (PR 42), the
same way, the other five's lines byte for byte as they were; ``glm_moe_dsa``'s
by PR 45, the other six's as they were (Moonlight's and Kimi Linear's latent
layers draw ``wq`` where they did: ``lm.mla_leaves`` with a null rank);
``evabyte``'s by PR 49, the other seven's as they were; ``minicpm_sala``'s
by PR 51, the other eight's as they were; ``mellum``'s by PR 58, the other
nine's as they were; ``nemotron_h``'s by PR 62, the other ten's as they were
(granite's and phi's ``A_log`` is drawn by ``lm.log_arange``, the function
their modules held); ``dots3_note``'s by PR 64, the other eleven's as they
were (``lm.mla_leaves`` takes the latent's geometry as a value and draws
Moonlight's, Kimi Linear's and GLM's leaves where it did); ``granite_moe``'s
(``models/granite.py`` with experts: ``granite-moe-tiny``) by PR 68, the
other twelve's as they were (a granite layer's expert leaves are drawn after
its other leaves, and a model without experts has none).

A PR that changes a family's draw on purpose records them again and says so;
one that does not must leave this file alone."""

import importlib
import json
import pathlib

import numpy as np
import pytest

FAMILIES = {"deepseek": "deepseek-tiny", "granite": "granite-tiny",
            "afmoe": "afmoe-tiny", "kimi_linear": "kimi-linear-tiny",
            "lfm2": "lfm2-tiny", "phi4flash": "phi4flash-tiny",
            "glm_moe_dsa": "glm-tiny", "evabyte": "evabyte-tiny",
            "minicpm_sala": "minicpm-sala-tiny", "mellum": "mellum-tiny",
            "nemotron_h": "nemotron-h-tiny", "dots3_note": "dots3-tiny",
            "granite_moe": "granite-moe-tiny"}
#: A family's second member: the module that holds it.
MODULES = {"granite_moe": "granite"}
PINNED = pathlib.Path(__file__).with_name("init_pinned.json")


def _checksum(leaf) -> float:
    """Sum of the elements, each times one of thirteen weights by its place:
    a swap of two elements or of two leaves' keys shows, as any one changed
    bit does."""
    flat = np.asarray(leaf, np.float64).ravel()
    return float(flat @ (1.0 + np.arange(flat.size) % 13))


def _leaves(family: str):
    """{"run/leaf": [shape, dtype, checksum]} of the family's tiny preset
    from ``PRNGKey(0)``, in the tree's own order."""
    import jax
    model = importlib.import_module(
        f"ray_tpu.models.{MODULES.get(family, family)}")
    params = model.init(model.config(FAMILIES[family]), jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {"/".join(str(key.key) for key in path):
            [list(leaf.shape), str(leaf.dtype), _checksum(leaf)]
            for path, leaf in flat}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_seeds_parameters_are_the_recorded_ones(family):
    want, got = json.loads(PINNED.read_text())[family], _leaves(family)
    assert list(got) == list(want)  # the keys, in the tree's order
    for name, (shape, dtype, checksum) in want.items():
        assert got[name][:2] == [shape, dtype], name
        assert got[name][2] == pytest.approx(checksum, rel=1e-12, abs=1e-12), \
            name


def test_the_checksum_sees_order_and_a_single_bit():
    leaf = np.arange(1.0, 40.0, dtype=np.float32)
    swapped = leaf.copy()
    swapped[[3, 4]] = swapped[[4, 3]]
    nudged = leaf.copy()
    nudged[17] = np.nextafter(nudged[17], np.float32(np.inf))
    assert len({_checksum(leaf), _checksum(swapped), _checksum(nudged)}) == 3


if __name__ == "__main__":
    print("{\n" + ",\n".join(
        f' "{family}": {{\n' + ",\n".join(
            f"  {json.dumps(name)}: {json.dumps(leaf)}"
            for name, leaf in _leaves(family).items()) + "\n }"
        for family in FAMILIES) + "\n}")
