"""A number the driver or the program counted in the window, as it is."""


def read(context, key):
    return context["window"].get(key)
