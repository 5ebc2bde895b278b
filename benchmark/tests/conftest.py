"""A configuration listed by a PR that may edit no file of the
benchmark brings its pin for ``test_profile.py`` as data
(``profile_pins.json``). The pins join the module's table when its
tests have been collected, which is before any of them runs, so that
``set(CONFIGS) == set(PINS)`` holds and every listed pool is still held
to the code it booted. The next ``benchmark`` PR moves them into the
table and deletes this file."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def pytest_collection_modifyitems(items):
    modules = {
        id(item.module): item.module for item in items
        if getattr(item, "module", None) is not None
        and item.module.__name__.rpartition(".")[2] == "test_profile"
    }
    if not modules:  # not among the files of this run
        return
    with open(os.path.join(HERE, "profile_pins.json"), encoding="utf-8") as f:
        brought = json.load(f)["pins"]
    for module in modules.values():
        for name, pin in brought.items():
            assert name not in module.PINS, f"{name}: pinned twice"
            module.PINS[name] = tuple(pin)
