"""Run the benchmark's tests after the repository's own: under the suite's
one-file-per-worker scheduling they then fill workers that have finished,
instead of holding up the longest files at the start."""


def pytest_collection_modifyitems(session, config, items):
    here = __file__.rsplit("/", 1)[0]
    items.sort(key=lambda item: str(item.fspath).startswith(here))
