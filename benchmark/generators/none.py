"""No arrivals: the system under test is a closed pipeline that feeds
itself (init). Present so that every traffic mix names a generator."""


def generate(run, fixtures=None) -> dict:
    return {}
