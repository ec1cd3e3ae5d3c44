"""The benchmark's configurations as the tests read them: one file of
``benchmark/configs/`` a deployment, by name."""

import glob
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> path of every configuration
CONFIGS = {
    os.path.basename(path)[:-len(".json")]: path
    for path in sorted(glob.glob(
        os.path.join(REPO, "benchmark", "configs", "*.json")))}


def config(name: str) -> dict:
    with open(CONFIGS[name]) as f:
        return json.load(f)
