"""Process start to ready to measure: corpus made and placed, engine built, every shape warm."""


def read(run):
    return run.setup_s
