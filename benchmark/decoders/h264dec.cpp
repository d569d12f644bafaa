// The benchmark's H.264 client decoder: the system libavcodec behind four C
// functions. Stands in for the browser's VideoDecoder. Copied from
// selkies_tpu/native/conformance.cpp (decoder part only), so that a later
// change to the program's test oracle cannot move the yardstick.
extern "C" {
#include <libavcodec/avcodec.h>
#include <libavutil/log.h>
}
#include <cstdint>
#include <cstring>

namespace {
struct Dec {
    AVCodecContext *ctx = nullptr;
    AVFrame *frame = nullptr;
    AVPacket *pkt = nullptr;
};
}  // namespace

extern "C" {

void *bench_h264_new() {
    av_log_set_level(AV_LOG_ERROR);   // an IDR stripe has a slice per MB
    const AVCodec *codec = avcodec_find_decoder(AV_CODEC_ID_H264);
    if (!codec) return nullptr;
    Dec *d = new Dec();
    d->ctx = avcodec_alloc_context3(codec);
    if (!d->ctx) { delete d; return nullptr; }
    d->ctx->flags |= AV_CODEC_FLAG_LOW_DELAY;   // no reordering in the stream
    d->ctx->thread_count = 1;
    if (avcodec_open2(d->ctx, codec, nullptr) < 0) {
        avcodec_free_context(&d->ctx);
        delete d;
        return nullptr;
    }
    d->frame = av_frame_alloc();
    d->pkt = av_packet_alloc();
    return d;
}

void bench_h264_free(void *h) {
    Dec *d = (Dec *)h;
    if (!d) return;
    if (d->pkt) av_packet_free(&d->pkt);
    if (d->frame) av_frame_free(&d->frame);
    if (d->ctx) avcodec_free_context(&d->ctx);
    delete d;
}

// One access unit in; 1 and the planes (tightly packed) out, 0 if the
// decoder gave no picture, negative on error.
int bench_h264_decode(void *h, const uint8_t *data, int64_t size,
                      uint8_t *y, uint8_t *u, uint8_t *v,
                      int64_t y_cap, int64_t c_cap, int *out_w, int *out_h) {
    Dec *d = (Dec *)h;
    if (!d) return -1;
    uint8_t *buf = (uint8_t *)av_malloc(size + AV_INPUT_BUFFER_PADDING_SIZE);
    if (!buf) return -1;
    memcpy(buf, data, size);
    memset(buf + size, 0, AV_INPUT_BUFFER_PADDING_SIZE);
    av_packet_unref(d->pkt);
    d->pkt->data = buf;
    d->pkt->size = (int)size;
    int rc = avcodec_send_packet(d->ctx, d->pkt);
    d->pkt->data = nullptr;
    d->pkt->size = 0;
    av_free(buf);
    if (rc < 0) return -3;
    int got = 0;
    while (true) {
        rc = avcodec_receive_frame(d->ctx, d->frame);
        if (rc == AVERROR(EAGAIN) || rc == AVERROR_EOF) break;
        if (rc < 0) return -4;
        const AVFrame *f = d->frame;
        const int w = f->width, ht = f->height;
        const int cw = (w + 1) / 2, ch = (ht + 1) / 2;
        if (f->format != AV_PIX_FMT_YUV420P && f->format != AV_PIX_FMT_YUVJ420P)
            return -2;
        if ((int64_t)w * ht > y_cap || (int64_t)cw * ch > c_cap) return -6;
        for (int r = 0; r < ht; ++r)
            memcpy(y + (size_t)r * w, f->data[0] + (size_t)r * f->linesize[0], w);
        for (int r = 0; r < ch; ++r) {
            memcpy(u + (size_t)r * cw, f->data[1] + (size_t)r * f->linesize[1], cw);
            memcpy(v + (size_t)r * cw, f->data[2] + (size_t)r * f->linesize[2], cw);
        }
        *out_w = w;
        *out_h = ht;
        got = 1;
    }
    return got;
}

}  // extern "C"
