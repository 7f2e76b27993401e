import pytest

from repro.core import trainer


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test starts from an empty shared-program cache
    (``core.trainer``): its evaluators build and trace their programs as
    a process's first evaluators do, whatever ran before it."""
    trainer.clear_programs()
