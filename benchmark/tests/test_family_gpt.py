"""``families/gpt.py`` and ``reference/gptj.py``'s ``arguments`` on the
configurations that name them: the program's config has the widths the file
publishes, at full and at tiny size, and a width that differs is reported."""

import os

import harness

family = harness.load_module("families", "gpt")
reference = harness.load_module("reference", "gptj")


def configs(group, name):
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config[group]["family"] == name:
            yield config


def test_the_program_runs_the_published_widths():
    for config in configs("program", "gpt"):
        cfg = family.config(config["program"])
        assert family.problems(config, cfg) == []
        assert family.vocab_size(cfg) == config["vocab_size"]
        tiny = family.tiny(config)
        assert family.problems(tiny, family.config(tiny["program"])) == []
        assert tiny["layout"]["mesh"] == config["layout"]["mesh"]


def test_a_width_that_differs_is_reported():
    for config in configs("program", "gpt"):
        cfg = family.config(config["program"])
        wrong = dict(config, n_embd=config["n_embd"] // 2, n_inner=None)
        assert len(family.problems(wrong, cfg)) == 2  # n_embd and 4 n_embd


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs("reference", "gptj"):
        assert reference.arguments(config) == {
            "rotary_dim": config["rotary_dim"],
            "eps": config["layer_norm_epsilon"]}
