"""A configuration states its own small size: the tests' copy is cut by
the configuration's ``test_sizes`` group, whatever its scale is called,
and only a configuration without the group by the old 20,000 people."""
import json
import os

from conftest import REPO_ROOT, small_copy


def add_a_second_deployment(root):
    """What a ``model_config`` PR brings: a file of its own whose scale
    is no number of ``people``."""
    with open(os.path.join(root, "benchmarks", "configs",
                           "other-sf10.json"), "w") as f:
        json.dump({"name": "other-sf10", "generator": "other",
                   "sizes": {"scale_factor": 10, "messages_per_person": 446},
                   "test_sizes": {"scale_factor": 0.003}}, f)


def sizes_of(root, config):
    with open(os.path.join(root, "benchmarks", "configs",
                           config + ".json")) as f:
        return json.load(f)["sizes"]


def test_a_configuration_is_cut_by_its_own_group(tmp_path):
    root = small_copy(tmp_path, add_a_second_deployment)
    # by its group and nothing else: no ``people`` key is pushed into it,
    # and what the group does not name keeps the deployment's shape
    assert sizes_of(root, "other-sf10") == {"scale_factor": 0.003,
                                            "messages_per_person": 446}


def test_a_configuration_without_the_group_is_cut_to_20000_people(small_root):
    committed = json.load(open(os.path.join(
        REPO_ROOT, "benchmarks", "configs", "fof-1m-50m.json")))
    assert "test_sizes" not in committed
    assert sizes_of(small_root, "fof-1m-50m") == {
        **committed["sizes"], "people": 20_000}
