"""Layer "program build and rewrite": the seconds the model's construction
takes, ``minimize`` and the AMP rewrite included (the benchmark's own span
around the driver's ``build``)."""


def read(ctx):
    return {"build.build_s": ctx["build_s"]}
