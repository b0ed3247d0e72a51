"""Global batch x whole steps in the window / the window's length, all
chips of the cell together. A sample is what one row of the batch holds: an
image for the image classifiers, one whole sequence (of the traffic mix's
``sequence_length``) for a token-sequence family; the name and the
arithmetic are the same for both."""


def read(context):
    return context["window_rate"]
