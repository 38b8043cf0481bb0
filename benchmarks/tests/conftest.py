import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: full-width AlexNet on the CPU, or the four-chip "
        "cell on four virtual CPU devices (the better part of an hour); "
        "run with -m slow")


def pytest_collection_modifyitems(config, items):
    if "slow" in (config.getoption("-m") or ""):
        return
    skip = pytest.mark.skip(reason="slow: run with -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
