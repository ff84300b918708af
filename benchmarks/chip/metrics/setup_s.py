"""Set-up: from process start to the first timed step or request, with
loading, compiling or reading compiled programs, and warming up."""


def read(record):
    return record["setup_s"]
