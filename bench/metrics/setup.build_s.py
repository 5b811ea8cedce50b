"""Host seconds of the model build: ``serve_secure.build`` (BN fusing,
secret sharing, weight limbs), the weights' generation excluded."""


def read(run):
    return run.spans.get("bench.build")
